"""spark_rapids_tpu — a TPU-native columnar SQL acceleration framework.

A brand-new framework with the capabilities of the RAPIDS Accelerator for
Apache Spark (reference mounted at /root/reference; see SURVEY.md): a
standalone dataframe/SQL engine whose physical plans are rewritten so that
supported operators execute as columnar batches resident in TPU HBM,
compiled to XLA (jax.numpy / Pallas) — with transparent per-operator host
fallback, an explain/tagging report, device admission control, a
device→host→disk spill hierarchy, and exchange expressed as XLA
collectives over the ICI mesh.

Quick start::

    import spark_rapids_tpu as srt
    sess = srt.Session()                     # TPU acceleration on
    df = sess.read_parquet("part.parquet")
    out = df.filter(df["x"] > 0).group_by("k").agg(srt.f.sum("x")).collect()
"""
from __future__ import annotations

import os

# int64/float64 columns require x64 mode
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax as _jax  # noqa: E402

_jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"

from . import types  # noqa: E402
from .config import TpuConf  # noqa: E402
from .data.column import (  # noqa: E402
    DeviceBatch,
    DeviceColumn,
    HostBatch,
    HostColumn,
    register_pytrees,
)

register_pytrees()

from .session import Session  # noqa: E402
from .plan import functions as f  # noqa: E402

__all__ = [
    "Session",
    "TpuConf",
    "types",
    "f",
    "HostBatch",
    "HostColumn",
    "DeviceBatch",
    "DeviceColumn",
    "__version__",
]

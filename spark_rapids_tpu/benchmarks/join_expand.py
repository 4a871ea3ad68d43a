"""How ``ops/kernels/join.py:expand_pairs`` should map its output slots to
their left rows, timed on the chip at the shapes the TPC-H cells hold.

Two mappings of the same inner join's ``c_out`` slots over ``nl`` left
rows, each a whole ``expand_pairs`` (lidx, ridx, slot_valid):

- ``search``: a ``searchsorted`` of every slot over the emit prefix sum
  and gathers of the row's offsets (``_expand_pairs(..., by_sort=False)``);
- ``sort``: one sort of the rows' markers with the slots, a carry scan,
  a second sort by place (``_expand_pairs(..., by_sort=True)``).

The two must agree to the bit; each is compiled once, warmed up and
timed over ``--reps`` calls that end in ``block_until_ready``.  One line
of JSON a shape; ``expand_by_sort`` is what the rule answers there::

    python -m spark_rapids_tpu.benchmarks.join_expand --out times.jsonl

Times come from a TPU only: on another backend the module refuses unless
``--rehearsal`` asks for small shapes, which it checks and does not time.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

#: (nl, c_out): q21's ``supplier`` join, q16's inner join, a mesh shard
#: after its exchange, a small expansion over a large side, q18's last
#: join (4,572 orders against lineitem), one between the two rules
SHAPES = ((1 << 21, 1 << 21), (1 << 23, 1 << 21), (1 << 24, 1 << 21),
          (1 << 23, 1 << 15), (1 << 13, 1 << 15), (1 << 21, 1 << 17))
REHEARSAL = ((1 << 12, 1 << 12), (1 << 14, 1 << 9), (1 << 9, 1 << 11))
#: right rows: each slot reads one of them
NR = 1 << 17


def inputs(nl: int, c_out: int, seed: int):
    """A Probe and emit counts of an inner join whose pairs fill ~90% of
    ``c_out``: each left row matches 0/1 right rows where the join is
    narrower than its side, more where it is wider."""
    import jax.numpy as jnp

    from ..ops.kernels.join import Probe

    rng = np.random.default_rng(seed)
    want = 0.9 * c_out
    if want <= nl:
        cnt = (rng.random(nl) < want / nl).astype(np.int32)
    else:
        cnt = rng.poisson(want / nl, nl).astype(np.int32)
        cnt[np.cumsum(cnt) > c_out] = 0
    lo = rng.integers(0, NR - 64, nl).astype(np.int32)
    p = Probe(jnp.asarray(rng.permutation(NR).astype(np.int32)),
              jnp.asarray(lo), jnp.asarray(cnt),
              jnp.zeros((NR,), jnp.bool_))
    return p, jnp.asarray(cnt), jnp.zeros((NR,), jnp.bool_)


def paths():
    from ..ops.kernels import join as J

    return {"search": lambda p, e, r, c: J._expand_pairs(p, e, r, c, False),
            "sort": lambda p, e, r, c: J._expand_pairs(p, e, r, c, True)}


def measure(nl: int, c_out: int, reps: int, seed: int, timed: bool):
    import jax

    from ..ops.kernels.join import expand_by_sort

    p, emit, r_extra = inputs(nl, c_out, seed)
    line = {"nl": nl, "c_out": c_out, "pairs": int(emit.sum()),
            "expand_by_sort": expand_by_sort(nl, c_out)}
    answers = {}
    for name, fn in paths().items():
        t0 = time.perf_counter()
        run = jax.jit(fn, static_argnums=(3,)).lower(
            p, emit, r_extra, c_out).compile()
        line[f"{name}_compile_s"] = round(time.perf_counter() - t0, 3)
        answers[name] = [np.asarray(x) for x in run(p, emit, r_extra)]
        if not timed:
            continue
        for _ in range(2):
            jax.block_until_ready(run(p, emit, r_extra))
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(run(p, emit, r_extra))
            times.append(time.perf_counter() - t0)
        line[f"{name}_ms"] = round(1e3 * statistics.median(times), 4)
        line[f"{name}_ms_range"] = [round(1e3 * min(times), 4),
                                    round(1e3 * max(times), 4)]
    want = answers["search"]
    line["agree"] = all(
        all(np.array_equal(a, b) for a, b in zip(got, want))
        for got in answers.values())
    return line


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=3900000001)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if not args.rehearsal and dev.platform != "tpu":
        print(f"no TPU ({dev.platform}): times come from a chip only",
              file=sys.stderr)
        return 2
    shapes = REHEARSAL if args.rehearsal else SHAPES
    out = None
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        out = open(args.out, "w")
    ok = True
    for nl, c_out in shapes:
        line = measure(nl, c_out, args.reps, args.seed, not args.rehearsal)
        line["device"] = f"{dev.platform} {dev.device_kind}"
        ok &= line["agree"]
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

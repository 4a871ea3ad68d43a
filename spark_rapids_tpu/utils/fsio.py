"""Atomic filesystem helpers shared by checkpointing and spill.

The one durable-write idiom of this engine: serialize into a temp file
in the SAME directory as the target, flush + fsync, then ``os.replace``
over the target.  A crash or SIGKILL mid-write leaves either the old
file or no file — never a truncated artifact a later reader could
mistake for valid data.  Crash-orphaned ``.tmp`` files are invisible to
readers (they never match the target name) and are swept by the
recovery hygiene pass.
"""
from __future__ import annotations

import json
import os
import tempfile

#: prefix of every in-flight temp file this module creates — the
#: recovery sweep removes stale ones; readers never match it
TMP_PREFIX = ".srt-tmp-"


def atomic_write_bytes(path: str, data) -> None:
    """Atomically write ``data`` (bytes / bytearray / a numpy uint8
    array via its buffer) to ``path``: temp file in the same directory,
    fsync, ``os.replace``."""
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=d, prefix=TMP_PREFIX, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(memoryview(data))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def atomic_write_json(path: str, obj, *, indent: int = 1) -> None:
    """Atomically write ``obj`` as JSON to ``path`` (same temp + fsync
    + replace discipline as :func:`atomic_write_bytes`)."""
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=d, prefix=TMP_PREFIX, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(obj, f, indent=indent, sort_keys=True)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def sweep_tmp_files(directory: str) -> int:
    """Remove crash-orphaned temp files under ``directory`` (recursive);
    returns the number removed.  Never raises."""
    removed = 0
    try:
        for root, _dirs, files in os.walk(directory):
            for name in files:
                if name.startswith(TMP_PREFIX):
                    try:
                        os.unlink(os.path.join(root, name))
                        removed += 1
                    except OSError:
                        pass
    except OSError:
        pass
    return removed

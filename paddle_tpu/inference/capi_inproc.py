"""In-process backend for the C inference/training API.

Reference parity: the reference's C API wraps an IN-PROCESS
``AnalysisPredictor`` (inference/capi/pd_predictor.cc) — no worker
process.  Here the C library embeds CPython (native/src/capi.cc
``PD_PredictorCreateInProcess``: ``Py_InitializeEx`` when standalone, or
the already-live interpreter when the .so is loaded from Python) and
calls this module directly, so predict/train runs in the SAME process on
the JAX/XLA backend.  The wire format is byte-identical to the pipe
worker's (capi_worker.py), parsed from memory instead of a pipe — one
protocol, two transports.
"""
from __future__ import annotations

import io
import struct
from typing import Dict, Tuple

from .capi_worker import handle_request

_predictors: Dict[int, Tuple[object, object, list, list]] = {}
_next_handle = [1]


def create(model_path: str) -> int:
    """Load a model package; returns an opaque handle for run()."""
    import os

    import paddle_tpu.static as static

    exe = static.Executor()
    scope = static.Scope()
    with static.scope_guard(scope):
        if os.path.isdir(model_path):
            program, feeds, fetches = static.load_inference_model(
                model_path, exe)
        else:
            program, feeds, fetches = static.load(model_path, exe)
    h = _next_handle[0]
    _next_handle[0] += 1
    _predictors[h] = (exe, program, list(fetches), scope)
    return h


def run(handle: int, request: bytes) -> bytes:
    """Execute one PDRQ request; returns a PDRS/PDER response — the SAME
    handler the pipe worker uses (capi_worker.handle_request), fed from
    memory instead of stdin.  An optional leading ``PDID | u64 id`` frame
    is accepted for client-code parity with the pipelined pipe worker and
    echoed back on the response; execution here is synchronous, so the id
    changes framing only, never ordering."""
    prefix = b""
    try:
        exe, program, fetches, scope = _predictors[handle]
        buf = io.BytesIO(request)
        magic = buf.read(4)
        if magic == b"PDID":
            prefix = b"PDID" + buf.read(8)
            if len(prefix) != 12:
                raise ValueError("truncated PDID frame")
            magic = buf.read(4)
        if magic != b"PDRQ":
            raise ValueError(f"bad request magic {magic!r}")
        return prefix + handle_request(buf, exe, program, fetches,
                                       scope=scope)
    except Exception as e:  # noqa: BLE001 — report over the wire
        msg = f"{type(e).__name__}: {e}".encode()
        return prefix + b"PDER" + struct.pack("<i", len(msg)) + msg


def destroy(handle: int) -> None:
    _predictors.pop(handle, None)

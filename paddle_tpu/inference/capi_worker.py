"""Executor service behind the C inference API.

Reference parity: the C API (paddle/fluid/inference/capi/pd_predictor.cc)
wraps the in-process C++ AnalysisPredictor.  In the TPU-native rebuild the
compute engine is JAX/XLA living in a Python process, so the C library
(native/src/capi.cc) runs THIS worker as a child process and speaks a
length-prefixed binary protocol over stdin/stdout — the C side stays a thin
zero-dependency client while inference executes on the real backend.  One
worker serves both roles of the reference's native surfaces: inference
(save_inference_model dirs; capi/) and train-from-saved-program
(static.save prefixes; train/demo/demo_trainer.cc) — scope state persists
across calls, so running a program whose ops include backward+optimizer
steps IS training.

Wire format (little-endian):
  request:  [b"PDID" | u64 id]  b"PDRQ" | i32 n_inputs | n x tensor
  tensor:   i32 name_len | name | i32 dtype | i32 ndim | i64 dims[] | data
  response: [b"PDID" | u64 id]  b"PDRS" | i32 n_outputs | n x tensor
  error:    [b"PDID" | u64 id]  b"PDER" | i32 len | utf-8 message
  decode:    b"PDID" | u64 id   b"PDGN" | i32 n | i64 tokens[n] | i32 max_new
  partial:   b"PDID" | u64 id   b"PDTK" | i32 n | i64 tokens[n]
  dtype codes: 0=f32 1=i32 2=i64 3=f64 4=u8 5=bool

The ``PDID`` frame is optional and opts a request into PIPELINING: the
client may send more id'd requests without waiting, the worker coalesces
them through the serving frontend (``paddle_tpu.serving.Server`` — padded
shape buckets, one executable per bucket), and id'd responses come back
PDID-tagged, possibly OUT OF ORDER.  Id'd requests must follow the
frontend contract: every feed shares its leading batch dim and every fetch
is row-independent with that batch dim (standard inference graphs; feeds
that don't fit fall back to a direct Executor run, still id-tagged).
Id-less requests are byte-identical to the legacy protocol: strict
request->response ordering on the direct Executor path, and each one acts
as a drain barrier — it is answered only after every in-flight id'd
request has completed.

``PDGN`` opens a STREAMING decode (always id'd — streams multiplex): the
prompt joins the worker's paged decoder (``serving/paged.py``, enabled by
``PDTPU_CAPI_DECODE=1``) and tokens come back incrementally as decode
steps complete — ``PDTK`` frames each carrying the tokens generated since
the last frame, terminated by a standard ``PDRS`` whose single ``tokens``
tensor is the full generation (or ``PDER``: admission refusal, eviction,
bad frame).  Multiple streams decode in ONE iteration-level batch, so
frames from different ids interleave.  The id-less drain barrier covers
decode streams too: a legacy request is answered only after every open
stream has terminated.  Knobs (env): ``PDTPU_CAPI_DECODE_BLOCKS`` (pool
blocks, default 64), ``_BLOCK_SIZE`` (8), ``_SEQS`` (4), ``_SEQ_BLOCKS``
(table width, 8), ``_CHUNK`` (prefill chunk, 8), ``_KV_DTYPE``
(float32|int8).
"""
from __future__ import annotations

import io
import os
import struct
import sys
import threading

import numpy as np

_DTYPES = {0: np.float32, 1: np.int32, 2: np.int64, 3: np.float64,
           4: np.uint8, 5: np.bool_}
_CODES = {np.dtype(v): k for k, v in _DTYPES.items()}


def _read_exact(f, n):
    buf = b""
    while len(buf) < n:
        chunk = f.read(n - len(buf))
        if not chunk:
            raise EOFError
        buf += chunk
    return buf


def _read_tensor(f):
    (name_len,) = struct.unpack("<i", _read_exact(f, 4))
    name = _read_exact(f, name_len).decode()
    dtype_code, ndim = struct.unpack("<ii", _read_exact(f, 8))
    dims = struct.unpack(f"<{ndim}q", _read_exact(f, 8 * ndim)) if ndim else ()
    dt = np.dtype(_DTYPES[dtype_code])
    n = int(np.prod(dims)) if dims else 1
    data = np.frombuffer(_read_exact(f, n * dt.itemsize), dtype=dt)
    return name, data.reshape(dims)

def _write_tensor(f, name, arr):
    arr = np.ascontiguousarray(arr)
    code = _CODES.get(arr.dtype)
    if code is None:  # e.g. bf16 fetches — promote to f32 over the wire
        arr = arr.astype(np.float32)
        code = 0
    nb = name.encode()
    f.write(struct.pack("<i", len(nb)) + nb)
    f.write(struct.pack("<ii", code, arr.ndim))
    f.write(struct.pack(f"<{arr.ndim}q", *arr.shape))
    f.write(arr.tobytes())


def _parse_feed(request_stream):
    """The feed dict of one PDRQ body (the magic is already consumed)."""
    (n_in,) = struct.unpack("<i", _read_exact(request_stream, 4))
    feed = {}
    for _ in range(n_in):
        name, arr = _read_tensor(request_stream)
        feed[name] = arr
    return feed


def _encode_results(fetches, results) -> bytes:
    out = io.BytesIO()
    out.write(b"PDRS" + struct.pack("<i", len(results)))
    for name, arr in zip(fetches, results):
        _write_tensor(out, str(name), np.asarray(arr))
    return out.getvalue()


def _encode_error(e: BaseException) -> bytes:
    msg = f"{type(e).__name__}: {e}".encode()
    return b"PDER" + struct.pack("<i", len(msg)) + msg


def handle_request(request_stream, exe, program, fetches, scope=None):
    """Parse one PDRQ request from ``request_stream`` and return the
    PDRS/PDER response bytes — the single protocol handler both
    transports share (pipe worker below; in-process capi_inproc)."""
    import contextlib

    import paddle_tpu.static as static

    try:
        feed = _parse_feed(request_stream)
        ctx = (static.scope_guard(scope) if scope is not None
               else contextlib.nullcontext())
        with ctx:
            results = exe.run(program, feed=feed, fetch_list=list(fetches))
        return _encode_results(fetches, results)
    except Exception as e:  # noqa: BLE001 — report over the wire
        return _encode_error(e)


class _Pipeline:
    """The worker's serving-frontend face: id'd requests submit here and
    complete (possibly out of order) on the dispatcher thread; ``drain``
    is the id-less barrier."""

    def __init__(self, program, feed_names, fetches, scope, respond):
        from ..serving import Server

        edges = os.environ.get("PDTPU_CAPI_BUCKETS", "1,2,4,8,16,32")
        wait_ms = float(os.environ.get("PDTPU_CAPI_MAX_WAIT_MS", "1.0"))
        self.server = Server(
            bucket_edges=tuple(int(e) for e in edges.split(",")),
            max_wait_ms=wait_ms)
        self.tenant = self.server.add_tenant(
            "capi", program, feed_names, list(fetches), scope)
        self.server.start()
        self.fetches = list(fetches)
        self._respond = respond
        self._pending = {}
        self._cond = threading.Condition()

    def submit(self, req_id: int, feed) -> bool:
        """True when accepted for pipelined dispatch; False when the feed
        doesn't fit the frontend contract (caller runs it directly)."""
        with self._cond:
            if req_id in self._pending:
                self._respond(req_id, _encode_error(ValueError(
                    f"duplicate in-flight request id {req_id}")))
                return True
            try:
                fut = self.server.submit("capi", feed)
            except ValueError:
                return False  # un-bucketable shape — direct path
            except Exception as e:  # noqa: BLE001 — report over the wire
                self._respond(req_id, _encode_error(e))
                return True
            self._pending[req_id] = fut
        fut.add_done_callback(lambda f, i=req_id: self._complete(i, f))
        return True

    def _complete(self, req_id, fut):
        try:
            payload = _encode_results(self.fetches, fut.result())
        except Exception as e:  # noqa: BLE001 — report over the wire
            payload = _encode_error(e)
        self._respond(req_id, payload)
        with self._cond:
            self._pending.pop(req_id, None)
            self._cond.notify_all()

    def drain(self):
        with self._cond:
            while self._pending:
                self._cond.wait()

    def close(self):
        self.server.close()


class _DecodeStreams:
    """The worker's paged-decode face: PDGN prompts join one
    iteration-level batch (``serving.PagedDecoder``) and a stepper thread
    pushes PDID-tagged PDTK deltas as tokens land, then the terminating
    PDRS.  ``drain`` is the legacy-request barrier, same contract as
    ``_Pipeline.drain``."""

    def __init__(self, respond):
        from ..serving import PagedDecoder, PagedKVCache, make_paged_toy_lm

        env = os.environ.get
        blocks = int(env("PDTPU_CAPI_DECODE_BLOCKS", "64"))
        block_size = int(env("PDTPU_CAPI_DECODE_BLOCK_SIZE", "8"))
        seqs = int(env("PDTPU_CAPI_DECODE_SEQS", "4"))
        seq_blocks = int(env("PDTPU_CAPI_DECODE_SEQ_BLOCKS", "8"))
        chunk = int(env("PDTPU_CAPI_DECODE_CHUNK", "8"))
        kv_dtype = env("PDTPU_CAPI_DECODE_KV_DTYPE", "float32")
        model = make_paged_toy_lm(
            max_positions=max(256, seq_blocks * block_size))
        cache = PagedKVCache(model, blocks, block_size, kv_dtype=kv_dtype)
        self.decoder = PagedDecoder(model, cache, seqs, seq_blocks,
                                    prefill_chunk=chunk, tenant="capi")
        self._respond = respond
        self._streams = {}           # req_id -> (handle, n_tokens_emitted)
        self._dec_lock = threading.Lock()   # joins vs the stepper thread
        self._cond = threading.Condition()
        self._closed = False
        self._thread = threading.Thread(
            target=self._step_loop, name="pdtpu-capi-decode", daemon=True)
        self._thread.start()

    def submit(self, req_id: int, prompt, max_new: int) -> None:
        from ..serving import AdmissionError

        with self._cond:
            if req_id in self._streams:
                self._respond(req_id, _encode_error(ValueError(
                    f"duplicate in-flight stream id {req_id}")))
                return
            try:
                with self._dec_lock:
                    h = self.decoder.join([int(t) for t in prompt], max_new)
            except (AdmissionError, ValueError) as e:
                self._respond(req_id, _encode_error(e))
                return
            self._streams[req_id] = [h, 0]
            self._cond.notify_all()

    def _step_loop(self):
        while True:
            with self._cond:
                while not self._streams and not self._closed:
                    self._cond.wait()
                if self._closed and not self._streams:
                    return
            with self._dec_lock:
                self.decoder.step()
            with self._cond:
                done = []
                for req_id, ent in self._streams.items():
                    h, emitted = ent
                    if len(h.tokens) > emitted:
                        delta = h.tokens[emitted:]
                        self._respond(req_id, b"PDTK" + struct.pack(
                            "<i", len(delta)) + struct.pack(
                            f"<{len(delta)}q", *delta))
                        ent[1] = len(h.tokens)
                    if h.done:
                        done.append(req_id)
                for req_id in done:
                    h, _ = self._streams.pop(req_id)
                    if h.evicted:
                        self._respond(req_id, _encode_error(RuntimeError(
                            "stream evicted mid-decode (KV pool "
                            f"pressure); {len(h.tokens)} tokens emitted")))
                    else:
                        self._respond(req_id, _encode_results(
                            ["tokens"], [np.asarray(h.tokens, np.int64)]))
                if done:
                    self._cond.notify_all()

    def drain(self):
        with self._cond:
            while self._streams:
                self._cond.wait()

    def close(self):
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout=5)


def _read_pdgn(inp):
    (n,) = struct.unpack("<i", _read_exact(inp, 4))
    prompt = struct.unpack(f"<{n}q", _read_exact(inp, 8 * n)) if n else ()
    (max_new,) = struct.unpack("<i", _read_exact(inp, 4))
    return list(prompt), max_new


def main():
    model_path = sys.argv[1]
    # The worker inherits the C client's environment (JAX_PLATFORMS
    # included) and is the ONE process that holds the chip: a client that
    # spawns it must not hold a JAX backend itself.  A Python host that
    # already does uses the in-process transport (capi_inproc /
    # PD_PredictorCreateInProcess) or spawns the worker with
    # JAX_PLATFORMS=cpu; otherwise backend start-up fails here, on stderr.
    import paddle_tpu.static as static

    exe = static.Executor()
    if os.path.isdir(model_path):
        program, feeds, fetches = static.load_inference_model(model_path, exe)
    else:
        program, feeds, fetches = static.load(model_path, exe)
    inp, out = sys.stdin.buffer, sys.stdout.buffer

    wlock = threading.Lock()

    def respond(req_id, payload):
        with wlock:
            if req_id is not None:
                out.write(b"PDID" + struct.pack("<Q", req_id))
            out.write(payload)
            out.flush()

    pipeline = None
    streams = None
    out.write(b"PDOK")
    out.flush()
    while True:
        try:
            magic = inp.read(4)
        except Exception:
            break
        req_id = None
        if magic == b"PDID":
            try:
                (req_id,) = struct.unpack("<Q", _read_exact(inp, 8))
                magic = _read_exact(inp, 4)
            except EOFError:
                break
        if magic == b"PDGN":
            # streaming decode: always id'd (frames multiplex over the pipe)
            try:
                prompt, max_new = _read_pdgn(inp)
            except EOFError:
                break
            if req_id is None:
                break  # id-less streams are a protocol violation
            if streams is None:
                if os.environ.get("PDTPU_CAPI_DECODE") != "1":
                    respond(req_id, _encode_error(RuntimeError(
                        "decode streaming disabled (set "
                        "PDTPU_CAPI_DECODE=1)")))
                    continue
                try:
                    streams = _DecodeStreams(respond)
                except Exception as e:  # noqa: BLE001 — report on the wire
                    respond(req_id, _encode_error(e))
                    continue
            streams.submit(req_id, prompt, max_new)
            continue
        if magic != b"PDRQ":
            break
        if req_id is not None:
            # pipelined path: coalesce through the serving frontend; the
            # request body must be consumed here (the stream is serial)
            # before the next frame can be read
            try:
                feed = _parse_feed(inp)
            except EOFError:
                break
            except Exception as e:  # noqa: BLE001 — report over the wire
                respond(req_id, _encode_error(e))
                continue
            if pipeline is None:
                try:
                    pipeline = _Pipeline(program, list(feeds), fetches,
                                         static.global_scope(), respond)
                except Exception:  # serving unavailable — direct fallback
                    pipeline = False
            if pipeline and pipeline.submit(req_id, feed):
                continue
            try:
                results = exe.run(program, feed=feed,
                                  fetch_list=list(fetches))
                respond(req_id, _encode_results(fetches, results))
            except Exception as e:  # noqa: BLE001 — report over the wire
                respond(req_id, _encode_error(e))
        else:
            # legacy path: drain the pipeline AND open decode streams
            # (ordering barrier), then the byte-identical strict
            # request->response protocol
            if pipeline:
                pipeline.drain()
            if streams:
                streams.drain()
            respond(None, handle_request(inp, exe, program, fetches))
    if pipeline:
        pipeline.close()
    if streams:
        streams.close()


if __name__ == "__main__":
    main()

"""Static-graph Executor: lower a Program to one jitted XLA computation.

Reference parity: `Executor::Run` (paddle/fluid/framework/executor.cc:180):
Prepare builds the op list (:378), RunPreparedContext interprets it
sequentially per op with kernel dispatch + GC (:476); python side
fluid/executor.py:474/:915 with feed/fetch injection and a prepared-context
cache (:1272).

TPU-native design (SURVEY.md §7 step 3): the op loop becomes a *trace* — the
Executor walks the block once inside jax.jit, invoking each op's lowering
rule to build a single fused XLA program `(feeds, donated_state,
carried_state, step) -> (fetches, new_state)`, cached by (program version,
feed signature, fetch list, donation mode).  State = every persistable
variable (parameters, optimizer slots, BN statistics, LR); the "write-back"
the reference does through Scope mutation becomes the functional state
round-trip — and with the `donate_state` flag on (default), the round-trip
is a buffer donation: XLA aliases the updated state onto the input buffers
and the Python-side write-back is a pointer swap, not a copy.  The PRNG
base key derives inside the compiled step from a per-entry seed and the
scalar `step` arg, so steady-state dispatch mints no host keys.  The `backward_region` pseudo-op (see
backward.py) differentiates a replay of the forward prefix; per-op
`fold_in`-derived PRNG scopes make the replay's random draws (dropout)
bit-identical to the primal's, so AD is exact.
"""
from __future__ import annotations

import contextlib
import re
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import random as _random
from ..utils import ledger as _ledger
from ..utils import monitor as _monitor
from ..utils import profiler as _profiler
from ..utils import trace as _trace
from . import ops as _ops  # registers lowerings
from .backward import GRAD_SUFFIX
from .framework import Program, Variable, default_main_program
from .registry import get_lowering

__all__ = ["Scope", "global_scope", "scope_guard", "Executor"]

# Test hook: force donation even where _donation_async_safe() says the
# platform serializes it (tests/test_fastpath.py covers the donation guard
# and parity paths on the CPU-only CI this way).
_FORCE_DONATION = False
_DONATE_PLATFORM_OK: Optional[bool] = None


def _donation_async_safe() -> bool:
    """Whether buffer donation keeps dispatch asynchronous on this backend.

    XLA:CPU executes a computation with donated inputs synchronously — the
    dispatch call blocks for the whole step, even when every donated buffer
    is already materialized (measured on jaxlib CPU: donated dispatch ==
    full step time, undonated dispatch ~10us).  Donating there would
    serialize the steady-state pipeline the fast path exists to build, so
    with `donate_state` on, CPU keeps device-resident state + async
    dispatch but skips `donate_argnums`; accelerator backends alias the
    buffers without giving up async dispatch and donate for real."""
    global _DONATE_PLATFORM_OK
    if _FORCE_DONATION:
        return True
    if _DONATE_PLATFORM_OK is None:
        _DONATE_PLATFORM_OK = jax.default_backend() != "cpu"
    return _DONATE_PLATFORM_OK


def _guard_stale(name: str, value):
    """Donation-safety guard: a scope entry whose buffer was donated into a
    compiled step (donate_state fast path) and consumed by XLA must fail
    legibly on read, not with XLA's 'Array has been deleted' crash.  Live
    values (the run scope's write-back) pass through untouched."""
    if isinstance(value, jax.Array) and value.is_deleted():
        from ..core.errors import StaleScopeValueError

        raise StaleScopeValueError(
            f"variable {name!r} holds a stale buffer: it was donated into a "
            "compiled Executor step (flag donate_state=1) and its device "
            "memory has been reused for the updated state.  Read the value "
            "from the scope the Executor ran on (the step's write-back "
            "replaced it there), or set PDTPU_FLAGS_donate_state=0 to "
            "restore copy semantics.")
    return value


class Scope:
    """Name -> host array store for persistables (ref framework/scope.h:46).

    Hierarchical like the reference: `new_scope()` creates a child whose
    lookups fall through to ancestors (the pattern the reference's
    per-thread/per-section scopes rely on); writes always land in the scope
    they are issued on (kid scopes never clobber the parent)."""

    def __init__(self, parent: "Optional[Scope]" = None):
        self._vars: Dict[str, Any] = {}
        self._parent = parent
        self._kids: List["Scope"] = []

    def new_scope(self) -> "Scope":
        kid = Scope(parent=self)
        self._kids.append(kid)
        return kid

    @property
    def parent(self) -> "Optional[Scope]":
        return self._parent

    def find_var(self, name: str):
        s: Optional[Scope] = self
        while s is not None:
            if name in s._vars:
                return _guard_stale(name, s._vars[name])
            s = s._parent
        return None

    def local_var(self, name: str):
        """Lookup without falling through to ancestors."""
        return _guard_stale(name, self._vars.get(name))

    def var(self, name: str):
        return self._vars.setdefault(name, None)

    def set(self, name: str, value):
        self._vars[name] = value

    def keys(self):
        return self._vars.keys()

    def drop_kids(self):
        """ref Scope::DropKids."""
        self._kids.clear()

    def drop(self):
        self._vars.clear()
        self._kids.clear()


_global_scope = Scope()
_scope_stack = [_global_scope]


def global_scope() -> Scope:
    return _scope_stack[-1]


class scope_guard:
    """ref fluid/executor.py scope_guard."""

    def __init__(self, scope: Scope):
        self.scope = scope

    def __enter__(self):
        _scope_stack.append(self.scope)
        return self.scope

    def __exit__(self, *exc):
        _scope_stack.pop()


def _run_op_traced(op, env, base_key, salt):
    """Execute one op's lowering under a per-op PRNG scope (deterministic
    replay for the backward region).  `salt` is unique per (block, op index)
    so sub-block randomness is trace-stable too."""
    lowering = get_lowering(op.type)
    ins = {slot: [env[n] for n in names] if names else []
           for slot, names in op.inputs.items()}
    with _random.rng_scope(jax.random.fold_in(base_key, salt)):
        outs = lowering(ins, op.attrs, op)
    for slot, names in op.outputs.items():
        vals = outs.get(slot, [])
        for name, val in zip(names, vals):
            env[name] = val


def _op_salt(block_idx: int, op_idx: int) -> int:
    return block_idx * 65536 + op_idx


def _xprof_scope_name(op_type: str, block_idx: int, op_idx: int) -> str:
    from ..utils.xprof import op_scope_name

    return op_scope_name(op_type, block_idx, op_idx)


def _trace_ops(program: Program, block_idx: int, ops, env, base_key,
               frozen=None):
    """Trace a list of ops (any block) with control-flow dispatch.

    ``frozen`` maps names to values that must stay bound to those exact
    (traced) values even when an op writes them — the backward replay
    injects differentiated intermediates this way, so ∂loss/∂v means "v as
    consumed downstream" rather than being recomputed by its producer
    (reference backward.py gradients() semantics).

    With the ``xprof_scopes`` flag on, every op (control-flow included, so
    sub-block ops nest under their parent's scope) traces inside
    ``jax.named_scope("<op_type>.b<block>.i<idx>")`` — op identity lands in
    optimized-HLO instruction metadata, survives fusion and AD, and
    utils/xprof.py joins per-instruction flops/bytes back to it.  Scopes
    are metadata-only: same HLO computation, same compile-cache key, same
    retrace behavior (pinned by tests/test_xprof.py)."""
    from ..core import flags as _flags

    scoped = bool(_flags.get_flag("xprof_scopes"))
    for idx, op in enumerate(ops):
        if op.type in ("feed", "fetch"):
            continue
        ctx = (jax.named_scope(_xprof_scope_name(op.type, block_idx, idx))
               if scoped else contextlib.nullcontext())
        with ctx:
            if op.type == "backward_region":
                _lower_backward(program, block_idx, ops, idx, env, base_key)
            elif op.type == "conditional_block":
                _lower_cond(program, op, env, base_key)
            elif op.type == "while":
                _lower_while(program, op, env, base_key)
            elif op.type == "static_rnn":
                _lower_static_rnn(program, op, env, base_key)
            else:
                salt = op.rng_salt if getattr(op, "rng_salt", None) \
                    is not None else _op_salt(block_idx, idx)
                _run_op_traced(op, env, base_key, salt)
        if frozen:
            env.update(frozen)


def _trace_block(program: Program, env: Dict[str, Any], base_key):
    """Walk block 0 building the computation into env."""
    _trace_ops(program, 0, program.global_block().ops, env, base_key)


def _arrays_only(env: Dict[str, Any]) -> Dict[str, Any]:
    """The sub-block closure snapshot passed through lax.cond/while must be a
    pytree of arrays."""
    out = {}
    for k, v in env.items():
        if hasattr(v, "dtype") or isinstance(v, (int, float, bool)):
            out[k] = jnp.asarray(v)
    return out


def _lower_cond(program, op, env, base_key):
    """conditional_block → jax.lax.cond over an env snapshot (ref
    operators/controlflow/conditional_block_op.cc — scoped sub-block run)."""
    tb = program.blocks[op.attrs["true_block"]]
    fb = program.blocks[op.attrs["false_block"]]
    pred = jnp.reshape(env[op.inputs["Cond"][0]], ()).astype(bool)
    snapshot = _arrays_only(env)

    def branch(block, out_names):
        def fn(captured):
            env2 = dict(captured)
            _trace_ops(program, block.idx, block.ops, env2, base_key)
            return tuple(env2[n] for n in out_names)
        return fn

    outs = jax.lax.cond(pred,
                        branch(tb, op.attrs["true_outs"]),
                        branch(fb, op.attrs["false_outs"]),
                        snapshot)
    for name, val in zip(op.outputs["Out"], outs):
        env[name] = val


def _lower_while(program, op, env, base_key):
    """while → jax.lax.while_loop with loop_vars as the carry (ref
    operators/controlflow/while_op.cc — here the carried Scope is explicit)."""
    cb = program.blocks[op.attrs["cond_block"]]
    bb = program.blocks[op.attrs["body_block"]]
    loop_names = op.inputs["X"]
    body_outs = op.attrs["body_outs"]
    cond_out = op.attrs["cond_out"]
    outer = _arrays_only(env)
    carry0 = tuple(jnp.asarray(env[n]) for n in loop_names)

    def with_carry(carry):
        env2 = dict(outer)
        env2.update(zip(loop_names, carry))
        return env2

    def cond_fun(carry):
        env2 = with_carry(carry)
        _trace_ops(program, cb.idx, cb.ops, env2, base_key)
        return jnp.reshape(env2[cond_out], ()).astype(bool)

    def body_fun(carry):
        env2 = with_carry(carry)
        _trace_ops(program, bb.idx, bb.ops, env2, base_key)
        return tuple(jnp.asarray(env2[n], carry[i].dtype)
                     for i, n in enumerate(body_outs))

    final = jax.lax.while_loop(cond_fun, body_fun, carry0)
    for name, val in zip(op.outputs["Out"], final):
        env[name] = val


def _lower_static_rnn(program, op, env, base_key):
    """static_rnn → jax.lax.scan over the time-major leading axis (ref
    operators/recurrent_op.cc; AD-of-scan replaces RecurrentGradOp)."""
    blk = program.blocks[op.attrs["rnn_block"]]
    step_in = op.attrs["step_in_names"]
    mem_names = op.attrs["mem_names"]
    mem_next = op.attrs["mem_next"]
    out_names = op.attrs["out_names"]
    outer = _arrays_only(env)
    seqs = tuple(jnp.asarray(env[n]) for n in op.inputs["X"])
    inits = tuple(jnp.asarray(env[n]) for n in op.inputs["Init"])

    def body(carry, xs_t):
        env2 = dict(outer)
        env2.update(zip(mem_names, carry))
        env2.update(zip(step_in, xs_t))
        _trace_ops(program, blk.idx, blk.ops, env2, base_key)
        new_carry = tuple(jnp.asarray(env2[n], carry[i].dtype)
                          for i, n in enumerate(mem_next))
        outs_t = tuple(env2[n] for n in out_names)
        return new_carry, outs_t

    _, stacked = jax.lax.scan(body, inits, seqs)
    for name, val in zip(op.outputs["Out"], stacked):
        env[name] = val


def _lower_backward(program, block_idx, ops, bw_idx, env, base_key):
    op = ops[bw_idx]
    loss_names = op.inputs["Loss"]
    param_names = op.inputs["Params"]
    grad_names = op.outputs["Grads"]
    # the replay closes over the *initial* bindings of everything except the
    # differentiated params — snapshot env entries that ops 0..bw_idx read
    init_env = dict(env)

    def replay(param_values: Dict[str, Any]):
        env2 = dict(init_env)
        env2.update(param_values)
        # freeze the differentiated names: a producer op in the replay must
        # not overwrite an injected intermediate (gradients()-wrt-
        # intermediate semantics, ref backward.py:1795)
        _trace_ops(program, block_idx, ops[:bw_idx], env2, base_key,
                   frozen=param_values)
        total = 0.0
        for ln in loss_names:
            total = total + jnp.sum(env2[ln].astype(jnp.float32))
        return total

    pv = {n: env[n] for n in param_names}
    grads = jax.grad(replay)(pv)
    for pname, gname in zip(param_names, grad_names):
        env[gname] = grads[pname]


# -- telemetry (utils/monitor.py; SURVEY §5.1) -------------------------------
# Registered at import so metricsdump lists them even before any run; every
# mutation is gated on the `metrics` flag inside the metric objects.
_m_cache_hit = _monitor.counter(
    "executor.cache_hit", "Executor.run compile-cache hits.")
_m_cache_miss = _monitor.counter(
    "executor.cache_miss", "Executor.run compile-cache misses (trace+compile).")
_m_compile_ms = _monitor.histogram(
    "executor.compile_time_ms",
    "Wall time of a cache-miss step: trace + XLA compile + first run (ms).")
_m_dispatch_ms = _monitor.histogram(
    "executor.dispatch_time_ms",
    "Host time a cache-hit (steady-state) Executor.run spends DISPATCHING "
    "the compiled step (ms).  Under async dispatch this returns before the "
    "device finishes — it measures the Python rim, not the device step; see "
    "executor.step_time_ms for the blocked wall time.")
_m_step_ms = _monitor.histogram(
    "executor.step_time_ms",
    "True steady-state step wall time (ms): dispatch plus blocking on one "
    "fetch until the device finishes.  Recorded only while the `metrics` "
    "flag is on — the block IS the cost of measuring; set "
    "PDTPU_FLAGS_metrics=0 to keep the fast path fully asynchronous.")
_m_donated_bytes = _monitor.gauge(
    "executor.donated_bytes", "Bytes of persistable state donated into the "
    "last step (device-resident, updated in place by XLA).",
    labelnames=("program",))
_m_prog_ops = _monitor.gauge(
    "executor.program_ops", "Op count of the last-compiled program "
    "(all blocks).", labelnames=("program",))
_m_state_bytes = _monitor.gauge(
    "executor.state_size_bytes", "Bytes of persistable state round-tripped "
    "through the last step.", labelnames=("program",))
_m_cost_flops = _monitor.gauge(
    "executor.cost_flops", "XLA cost_analysis() flop estimate of the "
    "last-compiled executable (absent when the backend exposes no cost "
    "model).", labelnames=("program",))
_m_cost_bytes = _monitor.gauge(
    "executor.cost_bytes_accessed", "XLA cost_analysis() bytes-accessed "
    "estimate of the last-compiled executable.", labelnames=("program",))
_m_traces = _monitor.counter(
    "executor.traces", "Program traces: how many times the Executor walked "
    "a Program's ops to (re)build a step function.  Increments at trace "
    "time only — steady-state dispatch of a compiled step never bumps it, "
    "and a warm persistent compile-cache start keeps it at 0 (the step "
    "deserializes instead of tracing).  A growing value in steady state is "
    "a retrace bug.")
# Device-memory profile of the last-compiled executable (utils/xprof.py over
# XLA memory_analysis(); the TPU-native stand-in for the reference's CUPTI
# memory counters).  Set whenever telemetry is on and the single-device AOT
# path compiled.
_m_mem_args = _monitor.gauge(
    "executor.device_mem_args_bytes", "memory_analysis() argument bytes of "
    "the last-compiled executable.", labelnames=("program",))
_m_mem_out = _monitor.gauge(
    "executor.device_mem_out_bytes", "memory_analysis() output bytes of the "
    "last-compiled executable.", labelnames=("program",))
_m_mem_temp = _monitor.gauge(
    "executor.device_mem_temp_bytes", "memory_analysis() temp (scratch) "
    "bytes of the last-compiled executable — the part of the memory "
    "footprint that is XLA's choice, not the model's.",
    labelnames=("program",))
_m_mem_code = _monitor.gauge(
    "executor.device_mem_code_bytes", "memory_analysis() generated-code "
    "bytes of the last-compiled executable.", labelnames=("program",))
_m_mem_total = _monitor.gauge(
    "executor.device_mem_total_bytes", "args + out + temp + code bytes of "
    "the last-compiled executable.", labelnames=("program",))
_m_predicted_peak = _monitor.gauge(
    "executor.predicted_peak_bytes", "memcheck's static per-device peak-HBM "
    "estimate for this program, set before the trace/compile it prices — "
    "compare against executor.device_mem_total_bytes to watch calibration "
    "in production.", labelnames=("program",))
# Collect-time census of what is actually resident: every live jax.Array in
# the process (donated state, prefetch staging, stray host copies included).
_m_mem_live_bytes = _monitor.gauge(
    "executor.device_mem_live_bytes", "Bytes of all live jax.Arrays in the "
    "process (jax.live_arrays() census, evaluated at collect time).")
_m_mem_live_count = _monitor.gauge(
    "executor.device_mem_live_arrays", "Count of live jax.Arrays in the "
    "process (jax.live_arrays() census, evaluated at collect time).")


def _census_field(field: str):
    def sample():
        from ..utils.xprof import live_array_census

        try:
            return float(live_array_census()[field])
        except Exception:
            return 0.0
    return sample


_m_mem_live_bytes.set_function(_census_field("bytes"))
_m_mem_live_count.set_function(_census_field("count"))


_prog_tokens = iter(range(1, 1 << 62))


def _program_token(program) -> int:
    """Stable per-Program cache token.  `id()` can alias after GC (round-1/2
    finding); a token stored ON the object cannot."""
    tok = getattr(program, "_exec_cache_token", None)
    if tok is None:
        tok = next(_prog_tokens)
        program._exec_cache_token = tok
    return tok


class _CacheEntry:
    """One compiled steady-state step plus everything needed to re-dispatch
    it without rebuilding signatures: the per-program key-prefix cache.  A
    steady-state `Executor.run` finds this via one dict lookup on the
    program's cache token and re-validates the feed shapes against
    ``feed_sig`` in place — no sorted-tuple signature is rebuilt, no program
    walk recomputes the persistable list."""

    __slots__ = ("key", "compiled", "version", "donate", "plan_token",
                 "fetch_names", "feed_sig", "state_names", "needs_value",
                 "op_count", "fingerprint", "kernel_fp", "disk_cache",
                 "aot", "mem")

    def __init__(self, key, version, donate, plan_token, fetch_names,
                 feed_arrays, state_names, needs_value, op_count, fingerprint,
                 kernel_fp=""):
        self.key = key
        self.compiled = None
        self.version = version
        self.donate = donate
        self.plan_token = plan_token
        self.fetch_names = list(fetch_names)
        self.feed_sig = {k: (tuple(v.shape), v.dtype)
                         for k, v in feed_arrays.items()}
        self.state_names = list(state_names)
        self.needs_value = frozenset(needs_value)
        self.op_count = op_count
        self.fingerprint = fingerprint
        self.kernel_fp = kernel_fp
        self.disk_cache = "off"  # persistent-cache provenance: hit|miss|off
        self.aot = None  # AOT executable when telemetry compiled one —
        self.mem = None  # xprof's attribution source + its memory breakdown

    def matches(self, version, fetch_names, feed_arrays, plan_token,
                donate, kernel_fp="") -> bool:
        if (self.version != version or self.donate != donate
                or self.plan_token != plan_token
                or self.kernel_fp != kernel_fp
                or self.fetch_names != fetch_names
                or len(self.feed_sig) != len(feed_arrays)):
            return False
        sig = self.feed_sig
        try:
            for k, v in feed_arrays.items():
                shape, dtype = sig[k]
                if v.shape != shape or v.dtype != dtype:
                    return False
        except KeyError:
            return False
        return True


class Executor:
    """ref fluid/executor.py:474.  `place` is accepted for API parity; XLA
    owns placement (SURVEY.md L0a TPU mapping)."""

    def __init__(self, place=None):
        self.place = place
        self._cache: Dict[Tuple, _CacheEntry] = {}
        # (program token, entry key) -> last entry; entry keys partition the
        # hot map so e.g. serving shape buckets each keep a pinned slot
        self._hot: Dict[Tuple, _CacheEntry] = {}
        self._step = 0

    # -- public API ----------------------------------------------------------
    def run(self, program=None, feed: Optional[dict] = None,
            fetch_list: Optional[Sequence] = None, scope: Optional[Scope] = None,
            return_numpy: bool = True, entry_key: Optional[str] = None):
        """Run one step of ``program``.

        ``entry_key`` names an independent steady-state entry point for the
        same program: each distinct key keeps its own hot-cache slot (and
        its own persistent-cache artifact), so a caller that legitimately
        alternates between several compiled shapes of one program — the
        serving frontend dispatching padded shape *buckets* — stays on the
        one-dict-lookup fast path for every bucket instead of thrashing the
        single per-program hot slot.  ``None`` (the default) preserves the
        historical one-hot-entry-per-program behavior.

        Steady-state fast path: with ``return_numpy=False`` the call is
        dispatch-asynchronous — it returns unmaterialized ``jax.Array``
        fetches as soon as XLA has enqueued the step, so host work (the next
        batch's collate, logging) overlaps device compute.  With the
        ``donate_state`` flag on (default), the persistable state pytree is
        donated into the compiled step: XLA updates parameters/optimizer
        slots in place and the scope write-back is a pointer swap, not a
        copy.  ``jax.Array`` feed values are passed through without a host
        round-trip (pair with ``io.DeviceFeeder`` prefetch)."""
        from .compiler import CompiledProgram

        plan = None
        if isinstance(program, CompiledProgram):
            # feed/fetch ride along so a plan="auto" resolution (the first
            # run only — the choice is memoized) prices real batch shapes
            plan = program._sharding_plan(feed=feed, fetch_list=fetch_list)
            program = program._program
        program = program or default_main_program()
        feed = feed or {}
        scope = scope or global_scope()

        fetch_names = [v.name if isinstance(v, Variable) else str(v)
                       for v in (fetch_list or [])]
        # device-resident feeds (DeviceFeeder prefetch) stay on device —
        # np.asarray on a jax.Array is a blocking D2H sync that would defeat
        # async dispatch; only host values are normalized to numpy
        feed_arrays = {k: v if isinstance(v, jax.Array) else np.asarray(v)
                       for k, v in feed.items()}

        from ..core import flags as _flags

        # donation follows the plan: the sharded fast path donates the
        # *sharded* state pytree (with_sharding's default), while the
        # data-parallel plan pins a place-once buffer-identity contract
        # (tests/test_static_dp.py) that in-place donation would break
        donate = (bool(_flags.get_flag("donate_state"))
                  and _donation_async_safe()
                  and (plan is None or plan.donate))
        plan_token = plan.token if plan is not None else None

        # hot path: one dict lookup on (program token, entry key), then an
        # in-place feed-shape check — no sorted signature tuple, no program
        # re-walk.  Distinct entry keys (shape buckets) never evict each
        # other's hot slot.
        hot_key = (getattr(program, "_exec_cache_token", None), entry_key)
        # kernel-config fingerprint (ops/pallas/config.py): kernel selection
        # happens at trace time, so a flag flip (or backend-gate change)
        # must be a clean recompile, never a stale hot-entry hit
        from ..ops.pallas import config as _pcfg

        kernel_fp = _pcfg.cache_key_part()
        entry = self._hot.get(hot_key)
        if entry is None or not entry.matches(program._version, fetch_names,
                                              feed_arrays, plan_token, donate,
                                              kernel_fp):
            entry = self._cold_lookup(program, fetch_names, feed_arrays,
                                      plan_token, donate, entry_key,
                                      kernel_fp)

        state, missing = {}, None
        for n in entry.state_names:
            v = scope.find_var(n)
            if v is None:
                if n in entry.needs_value:
                    missing = (missing or [])
                    missing.append(n)
            else:
                state[n] = v
        if missing:
            from ..core.errors import PreconditionNotMetError

            raise PreconditionNotMetError(
                f"persistable variables {missing} have no value in scope — "
                "run the startup program first (exe.run(startup_program))")

        # partition the state for donation: only buffers LOCAL to the run
        # scope are donated (fall-through reads must never clobber a parent
        # scope — ref framework/scope.h semantics), and a buffer aliased by
        # a feed or by a second state name is carried by copy so XLA never
        # sees the same donated buffer twice
        if donate:
            d_state: Dict[str, Any] = {}
            p_state: Dict[str, Any] = {}
            seen = {id(v) for v in feed_arrays.values()
                    if isinstance(v, jax.Array)}
            for n, v in state.items():
                if (isinstance(v, jax.Array) and id(v) not in seen
                        and scope.local_var(n) is v):
                    seen.add(id(v))
                    d_state[n] = v
                else:
                    p_state[n] = v
        else:
            d_state, p_state = {}, state

        token = entry.key[0]
        step_arg = np.int32(self._step)
        cache_miss = entry.compiled is None
        t_compile0 = time.perf_counter()
        if cache_miss:
            _m_cache_miss.inc()
            # calibration ledger: traced comm bytes accumulate in a
            # process-wide histogram, so the delta across this compile is
            # what *this* trace moved (utils/ledger.py joins it against
            # shardcheck's estimate); mem_report joins the memcheck leg
            ledger_pre = _ledger.pre_compile()
            mem_report = None
            with _trace.span("executor::trace_compile",
                             program=entry.fingerprint,
                             ops=entry.op_count) as sp:
                if _flags.get_flag("check_program"):
                    # pre-trace static analysis (SURVEY §7: fail fast and
                    # legibly before jit) — memoized by program version ×
                    # feed/fetch signature, so neither steady-state steps
                    # nor a second cold entry for the same program re-walk
                    from .analysis import check_program_cached \
                        as _check_program

                    _check_program(program, feed_names=set(feed_arrays),
                                   fetch_names=fetch_names)
                if plan is not None and _flags.get_flag("check_sharding"):
                    # tier-two: Program × ShardingPlan checks (SC001–SC009)
                    # — memoized by plan token × program version × feed
                    # shapes, zero steady-state cost
                    from .shardcheck import check_with_plan as _check_plan

                    _check_plan(program, plan, feed_arrays)
                if _flags.get_flag("check_memory"):
                    # tier-three: static peak-HBM pricing (MC001-MC007) —
                    # a predicted OOM aborts here, before the trace XLA
                    # would spend minutes on; advisory findings are
                    # flight-recorded, never raised.  Memoized like
                    # check_with_plan: zero steady-state cost
                    from .memcheck import check_memory_cached as _check_mem

                    mem_report = _check_mem(program, plan, feed_arrays,
                                            fetch_names)
                    if mem_report.mem is not None and _monitor.enabled():
                        _m_predicted_peak.set(
                            mem_report.mem.peak_bytes, program=str(token))
                    for d in mem_report.diagnostics:
                        _trace.flight_recorder().record(
                            "memcheck_violation", code=d.code,
                            severity=d.severity, var=d.var or "",
                            message=d.message)
                # verified graph-rewrite pipeline (static/passes.py):
                # compile-path only — hot-path steps never re-enter this
                # branch, and a verification failure rolls back to the
                # caller's program, so the step always compiles
                exec_program, passes_fp = program, ""
                _opt = _flags.get_flag("opt_passes")
                if _opt:
                    from . import passes as _passes

                    exec_program, passes_fp = _passes.optimize_for_executor(
                        program, _opt, feed_names=set(feed_arrays),
                        fetch_names=fetch_names, plan=plan,
                        feed_arrays=feed_arrays)
                    sp.set_attr("opt_passes", passes_fp or "rollback")
                seed = exec_program.random_seed or _random_seed()
                # persistent AOT cache (static/compile_cache.py): key the
                # artifact by program content × mesh/plan × versions; a hit
                # deserializes the compiled step instead of tracing it
                from . import compile_cache as _ccache

                disk = _ccache.active_cache()
                disk_key = None
                if disk is not None:
                    disk_key = _ccache.build_cache_key(
                        exec_program, seed, fetch_names, feed_arrays,
                        d_state, p_state, donate,
                        plan.fingerprint() if plan is not None else None,
                        entry=entry_key or "", passes=passes_fp,
                        kernel=entry.kernel_fp)
                (entry.compiled, entry.disk_cache, cost,
                 entry.aot) = self._build(
                    exec_program, fetch_names, entry.state_names, seed,
                    plan=plan, feed_arrays=feed_arrays, donate=donate,
                    example=(feed_arrays, d_state, p_state, step_arg),
                    disk=disk, disk_key=disk_key)
                sp.set_attr("compile_cache", entry.disk_cache)
                if entry.disk_cache == "hit":
                    _ccache._m_cc_hit.inc()
                elif entry.disk_cache == "miss":
                    _ccache._m_cc_miss.inc()
                if cost:
                    # XLA cost_analysis() of the compiled artifact:
                    # flops/bytes land on the compile span and as gauges —
                    # on persistent-cache hits too (the cost model is
                    # re-derived from the deserialized executable)
                    flops = cost.get("flops")
                    nbytes = cost.get("bytes accessed")
                    if flops is not None:
                        sp.set_attr("flops", float(flops))
                        _m_cost_flops.set(float(flops), program=str(token))
                    if nbytes is not None:
                        sp.set_attr("bytes_accessed", float(nbytes))
                        _m_cost_bytes.set(float(nbytes), program=str(token))
                if entry.aot is not None:
                    from ..utils import xprof as _xprof

                    entry.mem = _xprof.memory_stats(entry.aot)
                    if entry.mem and plan is not None:
                        # sharded build: when memory_analysis() priced a
                        # per-partition SPMD module, report the
                        # addressable-shard sum (this process's slice of
                        # the mesh) so memory_stats()/gauges cover meshes.
                        # Some backends (XLA:CPU) compile the module at
                        # global shapes instead — detected by comparing
                        # the reported args leg against the example's
                        # known global bytes; those are left unscaled.
                        mesh_l = plan.resolve_mesh()
                        try:
                            pi = jax.process_index()
                            n_local = sum(
                                1 for d in mesh_l.devices.flat
                                if d.process_index == pi) or 1
                        except Exception:
                            n_local = int(mesh_l.devices.size)
                        global_args = sum(
                            int(np.asarray(v).nbytes)
                            for part in (feed_arrays, d_state, p_state)
                            for v in (part or {}).values())
                        per_partition = (
                            entry.mem["args_bytes"] < 0.75 * global_args)
                        if n_local > 1 and per_partition:
                            entry.mem = {k: int(v) * n_local
                                         for k, v in entry.mem.items()}
                    if entry.mem:
                        prog = str(token)
                        _m_mem_args.set(entry.mem["args_bytes"], program=prog)
                        _m_mem_out.set(entry.mem["out_bytes"], program=prog)
                        _m_mem_temp.set(entry.mem["temp_bytes"], program=prog)
                        _m_mem_code.set(entry.mem["code_bytes"], program=prog)
                        _m_mem_total.set(entry.mem["total_bytes"],
                                         program=prog)
            if _monitor.enabled():
                _m_prog_ops.set(entry.op_count, program=str(token))
            # measured-vs-predicted compile record: joins estimate_comm /
            # estimate_peak / roofline against entry.mem and the traced
            # comm delta.  Guarded inside — an estimator bug degrades to
            # an unpriced record, never a failed run
            _ledger.observe_compile(entry=entry, program=program, plan=plan,
                                    feed_arrays=feed_arrays,
                                    fetch_names=fetch_names,
                                    mem_report=mem_report, pre=ledger_pre)
        else:
            _m_cache_hit.inc()

        if _monitor.enabled():
            _m_state_bytes.set(
                sum(getattr(v, "nbytes", 0) or 0 for v in state.values()),
                program=str(token))
            _m_donated_bytes.set(
                sum(getattr(v, "nbytes", 0) or 0 for v in d_state.values()),
                program=str(token))
        self._step += 1
        t_run0 = time.perf_counter()
        with _trace.span("executor::run", program=entry.fingerprint,
                         cache="miss" if cache_miss else "hit"):
            fetches, new_state = entry.compiled(feed_arrays, d_state,
                                                p_state, step_arg)
        now = time.perf_counter()
        # a miss's timing spans trace+compile+first run (XLA compiles on the
        # first jitted call); steady-state hits time only the dispatch —
        # under async dispatch the device may still be computing when
        # compiled() returns, so this is the Python-rim cost, not step time
        if cache_miss:
            from . import compile_cache as _ccache

            cold_ms = (now - t_compile0) * 1000.0
            _m_compile_ms.observe(cold_ms)
            # cold-start cost labeled by executable provenance: a warm
            # persistent cache (hit) should sit well below a real compile
            _ccache._m_cold_ms.observe(cold_ms, cache=entry.disk_cache)
        else:
            _m_dispatch_ms.observe((now - t_run0) * 1000.0)
        _trace.flight_recorder().record(
            "executor_run", name=entry.fingerprint,
            cache="miss" if cache_miss else "hit", ops=entry.op_count,
            dur_ms=round((now - t_run0) * 1000.0, 3))
        # pointer-swap write-back: under donation the arrays are already
        # device-resident and the old buffers were consumed in place
        for n, v in new_state.items():
            scope.set(n, v)
        if not cache_miss and _monitor.enabled():
            # true step time needs one device sync; only pay it while the
            # metrics flag is on (PDTPU_FLAGS_metrics=0 keeps full async)
            sync = fetches[0] if fetches else \
                next(iter(new_state.values()), None)
            if isinstance(sync, jax.Array):
                sync.block_until_ready()
                step_ms = (time.perf_counter() - t_run0) * 1000.0
                _m_step_ms.observe(step_ms)
                # same measured value feeds the calibration ledger's
                # steady-state window (a list append; the window closes
                # into a record every ledger_window steps)
                _ledger.observe_step(entry.fingerprint, step_ms)
        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return list(fetches)

    def _cold_lookup(self, program, fetch_names, feed_arrays, plan_token,
                     donate, entry_key=None, kernel_fp="") -> _CacheEntry:
        """Full cache-key build (sorted feed signature + program walk); the
        resulting entry is pinned on the hot map (keyed by program token ×
        entry key) so steady-state calls skip this entirely."""
        token = _program_token(program)
        key = (token, entry_key, program._version, tuple(fetch_names),
               tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in feed_arrays.items())),
               plan_token, donate, kernel_fp)
        entry = self._cache.get(key)
        if entry is None:
            state_names = self._state_names(program, global_scope())
            needs = [n for n in state_names if self._needs_value(program, n)]
            entry = _CacheEntry(
                key, program._version, donate, plan_token, fetch_names,
                feed_arrays, state_names, needs,
                op_count=sum(len(b.ops) for b in program.blocks),
                # cache token + program version identify the exact compiled
                # artifact on spans/flight events
                fingerprint=f"{token}v{program._version}",
                kernel_fp=kernel_fp)
            self._cache[key] = entry
        self._hot[(token, entry_key)] = entry
        return entry

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread: int = 0, debug: bool = False,
                           fetch_list=None, fetch_info=None,
                           print_period: int = 100,
                           prefetch_to_device=False):
        """ref fluid/executor.py:1597 train_from_dataset →
        TrainerFactory/MultiTrainer/DeviceWorker (trainer.h:41,
        device_worker.h:215 HogwildWorker threads pulling from the DataFeed
        channel).

        TPU-native collapse: the C++ DataFeed (native/src/datafeed.cc)
        already parses/shuffles/batches on background threads, and a single
        XLA device consumes steps in order — so the N-worker Hogwild loop
        becomes sequential jitted steps over the feed stream (`thread` is
        accepted for parity; parallel parsing is configured on the dataset
        via set_thread).

        ``prefetch_to_device=True`` (or a device) stages batch N+1 on the
        device from a background thread while batch N computes — the
        TPU-native replacement for the reference's DataFeed channel into
        per-thread DeviceWorkers (see io/prefetch.py)."""
        if dataset is None:
            raise ValueError("train_from_dataset requires a dataset")
        del thread  # parity knob; parse parallelism lives on the dataset
        fetch_list = list(fetch_list or [])
        names = [v.name if isinstance(v, Variable) else str(v)
                 for v in fetch_list]
        labels = list(fetch_info or names)
        stream = dataset
        if prefetch_to_device:
            from ..io.prefetch import DeviceFeeder

            stream = DeviceFeeder(
                dataset,
                device=None if prefetch_to_device is True
                else prefetch_to_device)
        step = 0
        last = None
        for batch in stream:
            last = self.run(program, feed=batch, fetch_list=fetch_list,
                            scope=scope)
            step += 1
            if debug and fetch_list and step % print_period == 0:
                msg = ", ".join(f"{l}={np.asarray(v).ravel()[:1][0]:.6g}"
                                for l, v in zip(labels, last))
                print(f"[train_from_dataset] step {step}: {msg}")
        return last

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread: int = 0, debug: bool = False,
                           fetch_list=None, fetch_info=None,
                           print_period: int = 100,
                           prefetch_to_device=False):
        """ref fluid/executor.py:1476 — same loop; the program is expected
        to be an inference/test clone (no optimizer ops)."""
        return self.train_from_dataset(program, dataset, scope, thread,
                                       debug, fetch_list, fetch_info,
                                       print_period, prefetch_to_device)

    # -- internals -----------------------------------------------------------
    def _state_names(self, program: Program, scope: Scope) -> List[str]:
        names = []
        for v in program.list_vars():
            if v.persistable:
                names.append(v.name)
        return names

    def _needs_value(self, program: Program, name: str) -> bool:
        """A persistable var needs a prior value unless some op in this
        program writes it before any read (init ops in startup programs)."""
        return self._first_access(program, program.global_block(), name) == "read"

    def _first_access(self, program: Program, block, name: str):
        """First access to `name` in execution order: 'read', 'write', or None.

        Walks cond/while/rnn sub-blocks at the point of their control-flow
        op.  Sub-block READS count — branch/body traces close over a
        snapshot of the enclosing env (`_lower_cond`/`_lower_while`), so an
        unset persistable read there fails just like a block-0 read.
        Sub-block WRITES do not — they mutate the branch-local env copy and
        escape only through the control-flow op's declared outputs, which
        the parent-level ``output_names()`` check already covers."""
        for op in block.ops:
            if name in op.input_names():
                return "read"
            for _a, sub_idx in op.sub_block_indices():
                sub = self._first_access(
                    program, program.blocks[sub_idx], name)
                if sub == "read":
                    return "read"
                # sub == 'write': local to that branch trace; a
                # write-then-read inside the sub-block was already
                # resolved locally (the recursion returned at the
                # write), so keep scanning the parent.
            if name in op.output_names():
                return "write"
        return None

    def _build(self, program: Program, fetch_names, state_names, seed,
               plan=None, feed_arrays=None, example=None, donate=False,
               disk=None, disk_key=None):
        """Trace the program into `(feeds, donated, carried, step) ->
        (fetches, new_state)`.  The PRNG base key is derived INSIDE the
        compiled function — `fold_in(PRNGKey(seed), step)` with `step`
        passed as a scalar arg — so steady-state calls never mint a host
        PRNGKey (a small jit dispatch of its own) and never retrace on the
        step counter.  `seed` is captured per compile-cache entry.

        Returns ``(compiled, disk_cache_status, xla_cost, aot)``: status is
        ``"hit"`` (step deserialized from ``compile_cache_dir`` — no trace,
        no lowering), ``"miss"`` (traced, exported, stored), or ``"off"``
        (persistent cache disabled or export unavailable); ``aot`` is the
        AOT-compiled executable when telemetry built one (the xprof
        attribution source), else None."""
        state_constraints: Dict[str, Any] = {}

        def raw(feeds, donated, carried, step):
            _m_traces.inc()  # host side effect: fires at trace time only
            env: Dict[str, Any] = {}
            env.update({k: jnp.asarray(v) for k, v in carried.items()})
            env.update({k: jnp.asarray(v) for k, v in donated.items()})
            env.update({k: jnp.asarray(v) for k, v in feeds.items()})
            base_key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
            # plan comm options (quantized/hierarchical gradient sync) are
            # ambient only while the body traces: axis-bound collective
            # lowerings consult parallel.compress.current_comm()
            comm_ctx = plan.comm_scope() if plan is not None \
                else contextlib.nullcontext()
            # likewise the plan's embedding-shard config: lookup_table
            # lowerings consult parallel.embedding.current_embedding() to
            # route covered tables through the all_to_all exchange
            emb_ctx = plan.embedding_scope(program) if plan is not None \
                else contextlib.nullcontext()
            with comm_ctx, emb_ctx:
                _trace_block(program, env, base_key)
            fetches = [env[n] for n in fetch_names]
            new_state = {}
            for n in state_names:
                if n in env:
                    v = env[n]
                    sh = state_constraints.get(n)
                    if sh is not None:
                        # pin the updated state to the plan's layout so
                        # steady-state write-backs come home already sharded
                        # and the placement rim passes them through
                        v = jax.lax.with_sharding_constraint(v, sh)
                    new_state[n] = v
            return fetches, new_state

        if plan is None:
            return self._build_single(raw, example, donate, disk, disk_key)
        # resolve which state leaves are embedding tables BEFORE placement:
        # state_shardings must see the bound names to vocab-shard them
        plan.bind_embedding_tables(program)
        from .memcheck import _optimizer_slots
        return self._build_sharded(raw, plan, example, donate,
                                   state_constraints, disk, disk_key,
                                   optimizer_slots=frozenset(
                                       _optimizer_slots(program)))

    @staticmethod
    def _load_or_export(raw, example, donate, disk, disk_key):
        """Resolve the core compiled step through the persistent cache.

        Hit: deserialize the ``jax.export`` artifact and jit its ``call``
        (donation re-applied via ``donate_argnums``) — the program is never
        traced and XLA never lowers it.  Miss: export once (the only trace
        of ``raw``), store atomically, and RUN the exported module too, so
        cold and warm processes execute the byte-identical artifact.  Any
        export-layer failure degrades to plain jit — the cache can only
        cost time, never a step."""
        donate_args = (1,) if donate else ()
        if disk is not None and disk_key is not None and example is not None:
            from jax import export as _export

            payload = disk.load(disk_key)
            if payload is not None:
                try:
                    exp = _export.deserialize(payload)
                    return (jax.jit(exp.call, donate_argnums=donate_args),
                            "hit")
                except Exception as e:
                    _trace.flight_recorder().record(
                        "compile_cache_deserialize_failed",
                        key=disk_key[:16], error=repr(e))
            try:
                exp = _export.export(jax.jit(raw))(*example)
                disk.store(disk_key, exp.serialize())
                return (jax.jit(exp.call, donate_argnums=donate_args),
                        "miss")
            except Exception as e:
                _trace.flight_recorder().record(
                    "compile_cache_export_failed", key=disk_key[:16],
                    error=repr(e))
        return jax.jit(raw, donate_argnums=donate_args), "off"

    # named-scope metadata in optimized HLO: op_name="...<type>.b<k>.i<j>..."
    _SCOPED_META_RE = re.compile(r'op_name="[^"]*\.b\d+\.i\d+')

    @staticmethod
    def _refresh_stale_metadata(core, example, aot, status):
        """Guard against jax's compilation caches serving an executable
        compiled before xprof scopes existed: the persistent cache key
        strips HLO metadata (cache_key.py runs strip-debuginfo), so a warm
        cache returns the old artifact and every op attributes to
        <unattributed> — and once loaded, the in-memory compilation memo
        pins it for the process, so no cache-config toggle can dislodge it.
        When scopes are on but none survived into the optimized HLO,
        recompile once with an explicit (default-valued, semantically
        no-op) compiler option: compile options ride both the in-memory
        memo key and the persistent key, so the scoped module resolves to
        its own entry — a real compile the first time, a cache hit in later
        processes.  Compile-cache *hits* are exempt: the deserialized
        artifact is authoritative and a recompile could not change its
        metadata."""
        from ..core import flags as _flags

        if (status == "hit" or not _flags.get_flag("xprof_scopes")
                or Executor._SCOPED_META_RE.search(aot.as_text())):
            return aot
        fresh = core.lower(*example).compile(
            compiler_options={"xla_embed_ir_in_executable": False})
        return (fresh if Executor._SCOPED_META_RE.search(fresh.as_text())
                else aot)

    @staticmethod
    def _build_single(raw, example, donate, disk=None, disk_key=None):
        """jit the traced step (donating the `donated` state subtree when the
        donate_state fast path is on); when telemetry is on, AOT-compile
        against the example args so the compiled artifact's
        `cost_analysis()` (flops / bytes accessed — XLA's replacement for
        the reference's per-op cost model), `memory_analysis()`, and the
        optimized HLO text (xprof attribution) are observable.  This runs
        on every persistent-cache status: a cache *hit*'s jitted
        ``exp.call`` would compile at first dispatch anyway, so AOT-
        compiling it up front re-derives the cost model at no extra
        compile — and never re-traces the program (``executor.traces``
        stays 0 on a warm start; the historical bug was cost gauges set
        only on the status-"off" path).  The AOT executable is pinned to
        the example's arg structure; a later call with a different state
        pytree (a program that grows persistables) falls back to the
        jitted path, which retraces as usual."""
        core, status = Executor._load_or_export(raw, example, donate, disk,
                                                disk_key)
        if example is None or not _monitor.enabled():
            return core, status, None, None
        # a compile failure (a Mosaic refusal, an HBM OOM) surfaces here,
        # once, with its own message — never swallowed into a second
        # compile at first dispatch
        aot = core.lower(*example).compile()
        aot = Executor._refresh_stale_metadata(core, example, aot, status)
        cost = None
        try:
            ca = aot.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if isinstance(ca, dict):
                cost = ca
        except Exception:
            pass

        def call(feeds, donated, carried, step):
            try:
                return aot(feeds, donated, carried, step)
            except TypeError:
                # the one structural case: a state pytree / aval that no
                # longer matches the example the AOT handle is pinned to.
                # It raises host-side before execution, so the donated
                # buffers are still live for the jitted (retracing) call.
                return core(feeds, donated, carried, step)

        return call, status, cost, aot

    @staticmethod
    def _build_sharded(raw, plan, example, donate, state_constraints,
                       disk=None, disk_key=None, optimizer_slots=None):
        """Sharded build: the SAME traced computation with feeds and
        persistable state placed by the ShardingPlan's NamedShardings.
        GSPMD partitions the compute and inserts the collectives the
        reference's MultiDevSSAGraphBuilder spelled out per gradient
        (ir/multi_devices_graph_pass/multi_devices_graph_pass.cc:464).

        The updated state is pinned to its input layout inside the traced
        step (``state_constraints`` feeds the `with_sharding_constraint` in
        ``raw``), so steady-state write-backs land already sharded and the
        placement rim below passes them through by identity: per-shard
        device residency across steps, donation of the sharded pytree
        included when the plan allows it (``with_sharding``; the
        data-parallel plan forbids it — the place-once contract in
        tests/test_static_dp.py pins buffer identity)."""
        mesh = plan.resolve_mesh()
        feeds0, d0, p0, step0 = example
        feed_sh = {k: plan.feed_sharding(k, v, mesh)
                   for k, v in feeds0.items()}
        state_all = dict(p0)
        state_all.update(d0)
        state_sh = plan.state_shardings(state_all, mesh,
                                        optimizer_slots=optimizer_slots)
        state_constraints.update(state_sh)

        def place(v, sh):
            # place-once: an array already laid out per the plan passes
            # through by identity (no device_put, no copy — what the DP
            # buffer-identity test and the donation path both rely on);
            # host values and stale layouts are placed
            if isinstance(v, jax.Array):
                try:
                    if v.sharding.is_equivalent_to(sh, v.ndim):
                        return v
                except Exception:
                    pass
            return jax.device_put(v, sh)

        def place_all(feeds, donated, carried):
            return ({k: place(v, feed_sh[k]) for k, v in feeds.items()},
                    {n: place(v, state_sh[n]) for n, v in donated.items()},
                    {n: place(v, state_sh[n]) for n, v in carried.items()})

        placed_example = None
        if disk is not None or _monitor.enabled():
            placed_example = (*place_all(feeds0, d0, p0), step0)
        core, status = Executor._load_or_export(raw, placed_example, donate,
                                                disk, disk_key)

        def call(feeds, donated, carried, step):
            pf, pd, pc = place_all(feeds, donated, carried)
            return core(pf, pd, pc, step)

        if placed_example is None or not _monitor.enabled():
            return call, status, None, None
        # AOT-compile the placed example so the sharded path reports
        # cost_analysis()/memory_analysis() like the single-device one —
        # the compiled module is the per-partition SPMD program, so its
        # memory numbers are per-device shards (memory_stats() scales them
        # to the addressable-shard sum).  Dispatch stays on the jitted
        # `core`: the AOT handle is observability-only here, the
        # per-shard attribution story remains a roadmap item.
        aot = core.lower(*placed_example).compile()
        cost = None
        try:
            ca = aot.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if isinstance(ca, dict):
                cost = ca
        except Exception:
            pass
        return call, status, cost, aot

    # -- observability (utils/xprof.py) --------------------------------------
    def memory_stats(self) -> Dict[str, int]:
        """Aggregate device-memory breakdown (memory_analysis()) over this
        Executor's hot compiled entries: args/out/temp/code/total bytes plus
        the contributing entry count.  Zeroes when nothing compiled with
        telemetry on — the serving TenantManager sums this across live
        tenants for its peak-temp gauges."""
        agg = {"args_bytes": 0, "out_bytes": 0, "temp_bytes": 0,
               "code_bytes": 0, "alias_bytes": 0, "total_bytes": 0,
               "programs": 0}
        seen = set()
        for entry in list(self._hot.values()):
            if id(entry) in seen or not entry.mem:
                continue
            seen.add(id(entry))
            agg["programs"] += 1
            for k, v in entry.mem.items():
                agg[k] = agg.get(k, 0) + int(v)
        return agg

    def xprof_report(self, program=None, entry_key: Optional[str] = None,
                     measured_ms: Optional[float] = None,
                     top: Optional[int] = None) -> Dict[str, Any]:
        """The xprof attribution/roofline report for a compiled entry (see
        utils/xprof.py): per-source-op regions with flops, bytes,
        compute/memory bound class, modeled time and MFU, anchored by the
        measured ``executor.step_time_ms`` median unless ``measured_ms``
        overrides it.  ``program=None`` with a single hot entry profiles
        that entry."""
        import math as _math

        from ..utils import xprof as _xprof

        entry = None
        if program is None:
            live = [e for e in self._hot.values() if e.aot is not None]
            entry = live[0] if len(live) == 1 else None
            if entry is None and len(live) > 1:
                raise ValueError(
                    "xprof_report(program=None) is ambiguous: "
                    f"{len(live)} profiled entries are live — pass the "
                    "program (and entry_key for shape buckets)")
        else:
            tok = getattr(program, "_exec_cache_token", None)
            entry = self._hot.get((tok, entry_key))
        if entry is None or entry.aot is None:
            raise RuntimeError(
                "no profiled executable for this program: xprof needs the "
                "`metrics` flag on at compile time, at least one "
                "Executor.run, and the single-device path (sharded entries "
                "are not yet attributable)")
        if measured_ms is None:
            p50 = _m_step_ms.percentile(50)
            if not _math.isnan(p50):
                measured_ms = p50
        report = _xprof.profile_aot(entry.aot, measured_ms=measured_ms,
                                    top=top)
        # publish to the telemetry plane: a live scrape of /xprof returns
        # the last report without re-profiling
        from ..utils import telemetry as _telemetry

        _telemetry.publish_snapshot("xprof", report)
        return report

    def close(self):
        self._cache.clear()
        self._hot.clear()


def _random_seed() -> int:
    # derive from the process-wide RNG stream so `paddle_tpu.seed` governs
    # static-graph randomness too
    key, counter = _random.get_rng_state()
    data = np.asarray(jax.random.key_data(key)).ravel()
    return (int(data[-1]) + int(counter)) & 0x7FFFFFFF

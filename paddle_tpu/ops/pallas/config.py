"""Kernel-selection config shared by the Pallas kernels and the Executor.

Three concerns live here so every kernel module and every dispatch site
agrees on them:

* **Gating** — `backend_is_tpu()` is the one platform predicate:
  `kernel_enabled(flag)` (the backend+flag gate every dispatch site uses)
  and `interpret()` (what every `pallas_call` passes as `interpret=`) both
  read it, so a dispatch that says "TPU" can never meet a kernel that says
  "interpret".  Production CPU paths never pay the interpret overhead;
  tests that want the Pallas branch on CPU CI monkeypatch `kernel_enabled`
  (the gate), which leaves the kernels in interpret mode.
* **Cache identity** — `fingerprint()` folds the *effective* kernel set
  (flag AND backend) into a short string the Executor joins into both its
  in-memory and persistent compile-cache keys.  Kernel selection happens
  at trace time, so two traces under different kernel configs are
  different executables: the fingerprint makes a flag flip a clean
  recompile instead of a stale cache hit, and keeps steady-state runs at
  zero retraces (pinned by tests/test_pallas_vision.py).
* **Honest attribution** — kernels register per-call cost models
  (`register_cost`) so utils/xprof.py can price the custom-call
  instructions a `pallas_call` lowers to (otherwise fused programs would
  drop out of the dot/conv flops model), and tools/kernelbench.py can
  report modeled-vs-measured roofline numbers from the same source.

Schema: bump `_SCHEMA` whenever a kernel's numerics or tiling change in a
way that invalidates cached executables compiled under the same flag set.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax

from paddle_tpu.core import flags
from paddle_tpu.utils import monitor

_SCHEMA = 1

# (short tag, flag name) for every Pallas kernel family, sorted by tag.
# The short tag keeps the fingerprint compact; the flag is the user knob.
_KERNEL_FLAGS: Tuple[Tuple[str, str], ...] = (
    ("conv", "use_pallas_conv_fused"),
    ("fa", "use_flash_attention"),
    ("int8", "use_pallas_int8"),
    ("ln", "use_fused_layer_norm"),
    ("pgat", "use_paged_attention"),
    ("pool", "use_pallas_pool"),
)


def backend_is_tpu() -> bool:
    """The one "am I on a TPU" predicate (platform name "tpu")."""
    return jax.default_backend() == "tpu"


def interpret() -> bool:
    """`interpret=` for every `pallas_call`: Mosaic on a TPU, the Pallas
    interpreter everywhere else."""
    return not backend_is_tpu()


def kernel_enabled(flag_name: str) -> bool:
    """Flag on AND a TPU backend (per-shape `supported()` gates are the
    kernel module's job, checked at the dispatch site)."""
    return bool(flags.get_flag(flag_name)) and backend_is_tpu()


def fingerprint() -> str:
    """Effective kernel set as a cache-key part, e.g.
    ``pk1:conv=1,fa=1,int8=1,ln=1,pool=1`` (all-zero off-TPU)."""
    bits = ",".join(f"{tag}={int(kernel_enabled(name))}"
                    for tag, name in _KERNEL_FLAGS)
    return f"pk{_SCHEMA}:{bits}"


def cache_key_part() -> str:
    """`fingerprint()` when any kernel is effective, else "" — an empty
    effective set traces exactly the pre-kernel executable, so legacy and
    CPU compile-cache keys stay byte-identical."""
    fp = fingerprint()
    return fp if "=1" in fp else ""


# ---------------------------------------------------------------------------
# Telemetry: which kernels actually ran, and which dispatches fell back.
# ---------------------------------------------------------------------------
_m_calls = monitor.counter(
    "pallas.kernel_calls",
    "Pallas kernel wrapper invocations (trace-time), labeled by kernel.",
    labelnames=("kernel",))
_m_fallbacks = monitor.counter(
    "pallas.fallbacks",
    "Dispatches that fell back to the XLA lowering, labeled kernel/reason.",
    labelnames=("kernel", "reason"))
_m_flash_tiles = monitor.gauge(
    "pallas.flash.tiles",
    "Score tiles a head in the flash_attention call traced last, by where "
    "they lie: wholly under the causal diagonal, on it, above it (skipped, "
    "never computed); each of the three kernels walks the same tiles.",
    labelnames=("kind",))


def record_call(kernel: str) -> None:
    _m_calls.inc(kernel=kernel)


def record_fallback(kernel: str, reason: str = "unsupported") -> None:
    _m_fallbacks.inc(kernel=kernel, reason=reason)


def record_flash_tiles(under_diagonal: int, on_diagonal: int,
                       skipped: int) -> None:
    """A head's score tiles at the blocks a flash_attention call runs
    (static shapes: known when the wrapper is traced)."""
    for kind, n in (("under_diagonal", under_diagonal),
                    ("on_diagonal", on_diagonal), ("skipped", skipped)):
        _m_flash_tiles.set(n, kind=kind)


def counted(kernel: str, supported: bool) -> bool:
    """A kernel's `supported()` verdict, passed through; a refusal is
    counted in `pallas.fallbacks` (the dispatch then takes the XLA path)."""
    if not supported:
        record_fallback(kernel)
    return supported


# ---------------------------------------------------------------------------
# Cost registry: kernel tag -> fn(HloInstr) -> flops.  Tags are the
# jax.named_scope strings the wrappers emit ("pallas.<kernel>"), matched
# as substrings of custom-call metadata op_name by utils/xprof.py.
# ---------------------------------------------------------------------------
_COSTS: Dict[str, Callable] = {}


def register_cost(tag: str, instr_flops_fn: Callable) -> None:
    _COSTS[tag] = instr_flops_fn
    from paddle_tpu.utils import xprof  # lazy: keep import-time deps light
    xprof.register_custom_call_cost(tag, instr_flops_fn)


def registered_costs() -> Dict[str, Callable]:
    return dict(_COSTS)

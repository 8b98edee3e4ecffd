"""Op-level cost attribution, roofline/MFU analysis, and device-memory
profiling over XLA's own cost model (``xprof``).

Reference parity: the reference pairs its host profiler with a CUPTI device
tracer (platform/device_tracer.h) so kernel time is attributable to the
framework op that launched it, and tools/timeline.py renders the join.  A
TPU has no CUPTI — and XLA fuses ops so aggressively that "which kernel"
is the wrong question anyway.  TPU-native design: attribution happens at
the *HLO metadata* layer instead of the driver layer.

* **Attribution** — the Executor's traced step wraps every lowered op in
  ``jax.named_scope("<op_type>.b<block>.i<idx>")`` (``@``/``:`` are eaten
  by XLA's scope sanitizer, so the encoding is dotted); the scope survives
  into each HLO instruction's ``metadata.op_name`` — through fusion, and
  through AD as ``jvp(<scope>)`` / ``transpose(jvp(<scope>))``, which means
  backward-pass FLOPs attribute to the *source* forward op.  A post-compile
  pass parses the optimized module text (``aot.as_text()``), models per-
  instruction flops and bytes from shapes (dot/conv get exact formulas,
  elementwise get element counts), and aggregates per source-op region and
  per op type.  ``cost_analysis()`` totals anchor the model (the
  ``flops_xla``/``bytes_xla`` fields).
* **Roofline / MFU** — a device peak table (TPU generations + a documented
  CPU fallback) classifies each region compute- vs memory-bound by
  arithmetic intensity vs the ridge point, models per-region time as
  ``max(flops/peak_flops, bytes/peak_bw)``, and computes per-region and
  whole-program MFU.  A measured step time (``executor.step_time_ms``)
  anchors the model; modeled-vs-measured drift is itself a report field —
  a drift ≫ 1 means the program is bound by something the roofline does
  not see (host overhead, collectives, serialization).
* **Memory** — ``memory_analysis()`` (args / outputs / temps / generated
  code) becomes the ``executor.device_mem_*`` gauges plus a per-program
  breakdown, and a ``jax.live_arrays()`` census tracks what is actually
  resident right now (the serving ``TenantManager`` layers peak-temp
  tracking across its live-executable LRU on top).

``python -m tools.xprof`` renders table / JSON views of the *modelled*
cost; measured device time is a ``jax.profiler`` capture
(``profiler.start_device_trace``) read in XProf/TensorBoard, where the step's
``REGIONS`` below show as the name scopes of every op.  The last built
report is flight-recorded (top regions + MFU) on post-mortem
dumps so a crash dump carries a perf snapshot.

Model limitations (documented, reported, never silently wrong): loop
bodies are counted once (trip counts are dynamic), custom-calls model 0
flops unless the owning kernel registered a cost model
(``register_custom_call_cost`` — every ops/pallas kernel does, keyed by
its ``pallas.<kernel>`` scope tag, so fused-kernel programs keep ≥90%
attribution coverage; bytes always count), and bytes are modeled at
fusion granularity — fused intermediates are register traffic, not HBM.
"""
from __future__ import annotations

import json
import math
import re
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import monitor as _monitor
from . import trace as _trace

__all__ = [
    "resolve_peaks", "parse_hlo", "attribute_hlo", "build_report",
    "profile_aot", "profile_jit", "memory_stats", "live_array_census",
    "render_table", "summarize", "last_summary",
    "OP_SCOPE_RE", "op_scope_name", "REGIONS", "ATTN_CORE", "SUBSCOPES",
    "PASSES", "step_region",
]

# -- telemetry (registered at import so metricsdump lists them) --------------
_m_reports = _monitor.counter(
    "xprof.reports", "xprof roofline/attribution reports built.")
_m_coverage = _monitor.gauge(
    "xprof.attribution_coverage", "Fraction of the last report's modeled "
    "flops attributed to named source ops (named_scope regions).")
_m_mfu = _monitor.gauge(
    "xprof.mfu", "Whole-program MFU of the last report (measured when a "
    "step time anchored it, else modeled).")

# ---------------------------------------------------------------------------
# Device peak table.
# ---------------------------------------------------------------------------
# (device_kind substring, peak dense flops/sec (bf16), peak HBM bytes/sec,
# HBM capacity bytes) per *jax device* — chips for v4+, cores for v2/v3.
# Public spec numbers; the table is deliberately coarse: the roofline
# classifies and ranks, it does not promise cycle accuracy.  The capacity
# column is what static/memcheck.py prices peak residency against (MC001).
_GB = 1 << 30
_TPU_PEAKS: Tuple[Tuple[str, float, float, int], ...] = (
    ("v6e", 918e12, 1640e9, 32 * _GB), ("trillium", 918e12, 1640e9, 32 * _GB),
    ("v5p", 459e12, 2765e9, 95 * _GB),
    ("v5 lite", 197e12, 819e9, 16 * _GB), ("v5e", 197e12, 819e9, 16 * _GB),
    ("v4", 275e12, 1228e9, 32 * _GB),
    ("v3", 61.5e12, 450e9, 16 * _GB),   # per core (2 cores/chip)
    ("v2", 22.5e12, 150e9, 8 * _GB),    # per core
)
# Order-of-magnitude CPU fallback (one host core running XLA:CPU): the
# absolute MFU is meaningless there, but the ridge point (5 flops/byte)
# still separates compute-bound matmuls from memory-bound elementwise, so
# classification and ranking work on CPU CI.  No HBM capacity: host RAM is
# not a budget memcheck can meaningfully enforce, so hbm_bytes stays None
# and MC001 only fires under an explicit capacity override.
_CPU_PEAK = (200e9, 40e9, None)


class PeakSpec:
    __slots__ = ("kind", "flops_per_sec", "bytes_per_sec", "source",
                 "hbm_bytes")

    def __init__(self, kind: str, flops_per_sec: float,
                 bytes_per_sec: float, source: str,
                 hbm_bytes: Optional[int] = None):
        self.kind = kind
        self.flops_per_sec = float(flops_per_sec)
        self.bytes_per_sec = float(bytes_per_sec)
        self.source = source
        # per-device HBM capacity in bytes; None when unknown (CPU fallback)
        self.hbm_bytes = None if hbm_bytes is None else int(hbm_bytes)

    @property
    def ridge(self) -> float:
        """Arithmetic intensity (flops/byte) where compute and memory time
        balance — AI above it is compute-bound."""
        return self.flops_per_sec / self.bytes_per_sec

    def to_json(self) -> Dict[str, Any]:
        return {"kind": self.kind, "peak_flops_per_sec": self.flops_per_sec,
                "peak_bytes_per_sec": self.bytes_per_sec,
                "ridge_flops_per_byte": round(self.ridge, 3),
                "hbm_bytes": self.hbm_bytes,
                "source": self.source}


def resolve_peaks(device_kind: Optional[str] = None,
                  peak_flops: Optional[float] = None,
                  peak_bytes_per_sec: Optional[float] = None,
                  platform: Optional[str] = None) -> PeakSpec:
    """The peak spec for ``device_kind`` (default: the first jax device,
    whose ``platform`` is then read too).  Explicit ``peak_flops``/
    ``peak_bytes_per_sec`` override the table — the escape hatch for new
    hardware.  A kind the table does not know gets the order-of-magnitude
    CPU spec on a CPU platform only: on any other platform it raises —
    a device that is not in the table is an error, not a default."""
    if device_kind is None:
        import jax

        dev = jax.devices()[0]
        device_kind, platform = dev.device_kind, dev.platform
    if peak_flops is not None and peak_bytes_per_sec is not None:
        return PeakSpec(device_kind, peak_flops, peak_bytes_per_sec,
                        "override")
    low = device_kind.lower()
    for sub, fl, bw, hbm in _TPU_PEAKS:
        if sub in low:
            return PeakSpec(device_kind, fl, bw, "table", hbm_bytes=hbm)
    if platform not in (None, "cpu"):
        raise ValueError(
            f"no peak spec for device_kind {device_kind!r} on platform "
            f"{platform!r}: add it to utils/xprof._TPU_PEAKS or pass "
            "peak_flops/peak_bytes_per_sec")
    fl, bw, hbm = _CPU_PEAK
    return PeakSpec(device_kind, fl, bw, "fallback", hbm_bytes=hbm)


# ---------------------------------------------------------------------------
# Optimized-HLO text parsing.
# ---------------------------------------------------------------------------
_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1, "f8e3m4": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,\s]*)\](?:\{[^}]*\})?")
_COMP_RE = re.compile(
    r"^(ENTRY\s+)?%?([\w.\-]+)\s+\([^)]*\)\s+->\s+.+\{\s*$")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
    r"(\([^)]*\)|[a-z0-9]+\[[0-9,\s]*\](?:\{[^}]*\})?)\s+"
    r"([\w\-]+)\((.*)$")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_LHS_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,\s]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")

# Regions: the executor encodes each lowered op as <op_type>.b<block>.i<idx>
# (see op_scope_name); AD wraps the component in jvp()/transpose().
OP_SCOPE_RE = re.compile(r"^([A-Za-z0-9_]+)\.b(\d+)\.i(\d+)$")
_WRAP_RE = re.compile(r"^([A-Za-z_][\w.\-]*)\((.+)\)$")

# flops = output element count for these opcodes (coarse: one op per lane)
_ELEMENTWISE = frozenset((
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "exponential", "exponential-minus-one", "log", "log-plus-one", "tanh",
    "logistic", "rsqrt", "sqrt", "cbrt", "negate", "abs", "sign", "floor",
    "ceil", "round-nearest-afz", "round-nearest-even", "compare", "select",
    "and", "or", "xor", "not", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "remainder", "atan2", "sine", "cosine", "tan",
    "expm1", "log1p", "is-finite", "clamp", "erf",
))
# pure data movement / bookkeeping: zero flops, and for the starred set the
# instruction itself also carries no HBM traffic (operands are counted by
# their consumers)
_ZERO_BYTES = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "iota",
))


# Regions of a compiled training step: the ``jax.named_scope`` names that
# text/pretrainer.py and nn/layer/transformer.py plant where the work is
# built.  Always on (HLO metadata only), not behind ``xprof_scopes``.  The
# innermost one in an instruction's ``metadata.op_name`` is its region;
# backward work lands in the same region (``transpose(jvp(<scope>))``).
# ``core`` counts only directly inside ``attn`` (the path reads
# ``attn/.../core``); what is under ``encoder`` and in no finer region is
# the scan's own bookkeeping (stacking and slicing residuals).  A Layer
# attribute of the same name (``ErnieModel.encoder``) reads as that region.
REGION_EMBED = "embed"          # lookups, position/type add, embedding LN
REGION_ENCODER = "encoder"      # the whole block stack
REGION_ATTN = "attn"            # the token mixer: q/k/v/out projections +
                                # the core, a convolution mixer (`conv`), a
                                # state-space mixer (`ssm`) or a delta-rule
                                # mixer (`gdn`)
REGION_ATTN_CORE = "core"       # scores -> softmax -> values, under attn
REGION_FFN = "ffn"              # both products and the activation
REGION_LN = "ln"                # residual add, dropout, LayerNorm
REGION_HEAD = "head"            # MLM transform + tied logits + NSP
REGION_LOSS = "loss"            # cross-entropies
REGION_OPTIMIZER = "optimizer"  # the update over every leaf, grad casts
ATTN_CORE = f"{REGION_ATTN}/{REGION_ATTN_CORE}"   # as reports name it
# finer scopes inside a region (an expert layer's parts under `ffn`, a
# mixer that is no attention under `attn`): a region reader ignores them, a
# sub-scope reader finds them as `<region>/<sub>`
SCOPE_ROUTER = "router"         # ffn: scores, selection, weights
SCOPE_EXPERTS = "experts"       # ffn: sort, dispatch, grouped products, combine
SCOPE_SHARED = "shared"         # ffn: the shared experts
SCOPE_CONV = "conv"             # attn: a gated short-convolution mixer, whole
SCOPE_SSM = "ssm"               # attn: a Mamba-2 mixer, whole (in-projection,
                                # convolution, scan, gated norm, out-projection)
SCOPE_SSD = "ssd"               # attn/ssm: the state-space scan alone
                                # (`ops/ssd.py`; `scan` is the block stack's
                                # bookkeeping, `core` is attention's)
SCOPE_GDN = "gdn"               # attn: a Gated DeltaNet mixer, whole (its
                                # `proj` and `pointwise` as under `ssm`)
SCOPE_DELTA = "delta"           # attn/gdn: the gated delta rule alone
                                # (`ops/delta_rule.py`)
# the second level: what a mixer or the expert layer does, pass by pass
SCOPE_PROJ = "proj"             # attn, attn/conv, attn/ssm: the projections'
                                # products and nothing else
SCOPE_PREP = "prep"             # attn: all between the projections and the
                                # core, both ways (head split and merge,
                                # splits, concatenations, rotary, q/k norm)
SCOPE_POINTWISE = "pointwise"   # attn/conv, attn/ssm: what a mixer does that
                                # is neither a product nor the scan
SCOPE_DISPATCH = "dispatch"     # ffn/experts: the tokens' rows into sorted
                                # order; the per-token gather-and-sum back
SCOPE_PRODUCTS = "products"     # ffn/experts: the grouped products alone
SCOPE_GATED = "gated"           # ffn/experts: the activation between them
SCOPE_COMBINE = "combine"       # ffn/experts: the weighted sum back per token
REGIONS = (REGION_EMBED, REGION_ENCODER, REGION_ATTN, ATTN_CORE, REGION_FFN,
           REGION_LN, REGION_HEAD, REGION_LOSS, REGION_OPTIMIZER)
# every scope under a region, as a path from it -> the scopes planted
# directly inside: the one list that the program, its tests and the
# benchmark's tools read.  The sort (`DroplessMoE._plan`) stays directly
# under `experts`.
SUBSCOPES = {
    REGION_ATTN: (REGION_ATTN_CORE, SCOPE_PROJ, SCOPE_PREP, SCOPE_CONV,
                  SCOPE_SSM),
    f"{REGION_ATTN}/{SCOPE_CONV}": (SCOPE_PROJ, SCOPE_POINTWISE),
    f"{REGION_ATTN}/{SCOPE_SSM}": (SCOPE_PROJ, SCOPE_POINTWISE, SCOPE_SSD),
    REGION_FFN: (SCOPE_ROUTER, SCOPE_EXPERTS, SCOPE_SHARED),
    f"{REGION_FFN}/{SCOPE_EXPERTS}": (SCOPE_DISPATCH, SCOPE_PRODUCTS,
                                      SCOPE_GATED, SCOPE_COMBINE),
}
# the passes `step_region` tells apart
PASSES = ("fwd", "remat", "bwd")


def op_scope_name(op_type: str, block_idx: int, op_idx: int) -> str:
    """The named-scope encoding the Executor plants per lowered op.  Dotted
    — XLA's scope sanitizer truncates ``@`` and ``:`` out of
    ``metadata.op_name`` (measured), so ``mul@0:3`` would arrive as just
    ``mul``; ``mul.b0.i3`` survives intact."""
    return f"{op_type}.b{block_idx}.i{op_idx}"


class HloInstr:
    __slots__ = ("name", "opcode", "out_shapes", "operand_shapes", "op_name",
                 "rest")

    def __init__(self, name, opcode, out_shapes, operand_shapes, op_name,
                 rest):
        self.name = name
        self.opcode = opcode
        self.out_shapes = out_shapes          # [(dtype, (dims...)), ...]
        self.operand_shapes = operand_shapes
        self.op_name = op_name
        self.rest = rest                      # attr tail for dot/conv/calls


def _parse_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    out = []
    for dtype, dims in _SHAPE_RE.findall(text):
        try:
            shape = tuple(int(d) for d in dims.replace(" ", "").split(",")
                          if d != "")
        except ValueError:
            shape = ()
        out.append((dtype, shape))
    return out


def _elems(shape: Tuple[int, ...]) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def _shape_bytes(dtype: str, shape: Tuple[int, ...]) -> int:
    return _elems(shape) * _DTYPE_BYTES.get(dtype, 4)


def _operand_text(rest: str) -> str:
    """``rest`` starts right after ``opcode(``: the text up to the paren
    that closes the operand list."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                return rest[:i]
    return rest


_OPERAND_RE = re.compile(
    r"(?:([a-z0-9]+\[[0-9,\s]*\])(?:\{[^}]*\})?\)?\s+)?%([\w.\-]+)")


def parse_hlo(text: str) -> Tuple[Dict[str, List[HloInstr]], List[str]]:
    """Parse HLO module text into {computation name: [instructions]} plus
    the list of ENTRY computation names (one per module in the text).

    An operand is ``<shape> %<name>`` in older text and a bare ``%<name>``
    since jax 0.9: there its shape is the output shape of the instruction
    (a parameter included) that defines the name earlier in the same
    computation.  A tuple-shaped operand counts by its last element."""
    comps: Dict[str, List[HloInstr]] = {}
    entries: List[str] = []
    current: Optional[List[HloInstr]] = None
    defined: Dict[str, List[Tuple[str, Tuple[int, ...]]]] = {}
    for line in text.splitlines():
        m = _COMP_RE.match(line)
        if m is not None:
            current = comps.setdefault(m.group(2), [])
            defined = {}
            if m.group(1):
                entries.append(m.group(2))
            continue
        if current is None:
            continue
        if line.strip() == "}":
            current = None
            continue
        mi = _INSTR_RE.match(line)
        if mi is None:
            continue
        name, out_type, opcode, rest = mi.groups()
        op_name_m = _OPNAME_RE.search(rest)
        operands = []
        for inline, ref in _OPERAND_RE.findall(_operand_text(rest)):
            if inline:
                operands += _parse_shapes(inline)
            elif defined.get(ref):
                operands.append(defined[ref][-1])
        out_shapes = _parse_shapes(out_type)
        defined[name] = out_shapes
        current.append(HloInstr(
            name, opcode, out_shapes, operands,
            op_name_m.group(1) if op_name_m else "", rest))
    return comps, entries


# custom-call cost registry: Pallas kernels lower to custom-call
# instructions XLA's shape-based model cannot price, so each kernel
# wrapper emits a ``jax.named_scope("pallas.<kernel>")`` tag (it survives
# into metadata.op_name) and registers fn(HloInstr) -> flops here via
# ops/pallas/config.register_cost.  Bytes need no registry: custom-call
# operand/output bytes are already counted by _instr_bytes.
_CUSTOM_CALL_COSTS: Dict[str, Any] = {}


def register_custom_call_cost(tag: str, instr_flops_fn) -> None:
    """Price custom-call instructions whose metadata op_name contains
    ``tag`` with ``instr_flops_fn(instr) -> flops``."""
    _CUSTOM_CALL_COSTS[tag] = instr_flops_fn


def _custom_call_flops(instr: HloInstr) -> float:
    for tag, fn in _CUSTOM_CALL_COSTS.items():
        if tag in instr.op_name:
            try:
                return float(fn(instr))
            except Exception:
                return 0.0
    return 0.0


def _instr_flops(instr: HloInstr) -> float:
    op = instr.opcode
    if not instr.out_shapes:
        return 0.0
    out_elems = sum(_elems(s) for _, s in instr.out_shapes)
    if op == "custom-call":
        return _custom_call_flops(instr)
    if op == "dot":
        m = _LHS_CDIMS_RE.search(instr.rest)
        if m is None or not instr.operand_shapes:
            return 2.0 * out_elems
        lhs = instr.operand_shapes[0][1]
        contracted = 1
        for d in (int(x) for x in m.group(1).replace(" ", "").split(",")
                  if x != ""):
            if d < len(lhs):
                contracted *= lhs[d]
        return 2.0 * out_elems * contracted
    if op == "convolution":
        # flops = 2 * out_elems * (kernel taps per output element); the rhs
        # dims minus its 'o' (output-feature) dim are exactly those taps —
        # grouped convs included, since rhs 'i' is already per-group
        m = _DIM_LABELS_RE.search(instr.rest)
        if m is None or len(instr.operand_shapes) < 2:
            return 2.0 * out_elems
        rhs_labels = m.group(2)
        rhs = instr.operand_shapes[1][1]
        taps = 1
        for i, lab in enumerate(rhs_labels):
            if lab != "o" and i < len(rhs):
                taps *= rhs[i]
        return 2.0 * out_elems * taps
    if op in ("reduce", "reduce-window"):
        return float(sum(_elems(s) for _, s in instr.operand_shapes[:1])
                     or out_elems)
    # sparse-lookup pricing (parallel/embedding.py exchange): one
    # address-compute+load per gathered element, one accumulate per
    # scattered update element — so an embedding backward's cost scales
    # with batch ids, never with vocab size
    if op == "gather":
        return float(out_elems)
    if op == "scatter":
        # operands = (target, indices, updates): pay for the update rows
        if len(instr.operand_shapes) >= 3:
            return float(_elems(instr.operand_shapes[2][1]))
        return float(out_elems)
    if op == "dynamic-update-slice":
        if len(instr.operand_shapes) >= 2:
            return float(_elems(instr.operand_shapes[1][1]))
        return float(out_elems)
    if op in _ELEMENTWISE:
        return float(out_elems)
    return 0.0


def _instr_bytes(instr: HloInstr) -> float:
    if instr.opcode in _ZERO_BYTES:
        return 0.0
    total = sum(_shape_bytes(d, s) for d, s in instr.out_shapes)
    total += sum(_shape_bytes(d, s) for d, s in instr.operand_shapes)
    return float(total)


def _unwrap(component: str) -> str:
    """Strip transform wrappers — ``transpose(jvp(X))`` → ``X`` — so
    backward-pass instructions attribute to their forward source scope."""
    while True:
        m = _WRAP_RE.match(component)
        if m is None:
            return component
        component = m.group(2)


_JIT_RE = re.compile(r"\bp?jit\([^()]*\)")
_WRAPPERS_RE = re.compile(r"[\w.\-]+\(|\)")


def step_region(op_name: str) -> Tuple[Optional[str], str]:
    """(region, pass) of one instruction's ``metadata.op_name``: the
    innermost of ``REGIONS`` among the path's scopes (``attn/core`` where
    ``core`` sits directly under ``attn``), None where the path names none;
    pass is one of ``PASSES``: ``"remat"`` where the path goes through
    ``rematted_computation`` (a ``jax.checkpoint``'s recomputed forward, as
    JAX itself marks it), else ``"bwd"`` where it went through
    ``transpose(`` (the backward of that scope), else ``"fwd"``."""
    comps = _WRAPPERS_RE.sub("", _JIT_RE.sub("", op_name)).split("/")
    which = ("remat" if "rematted_computation" in comps
             else "bwd" if "transpose(" in op_name else "fwd")
    for i in range(len(comps) - 1, -1, -1):
        if comps[i] == REGION_ATTN_CORE and i and comps[i - 1] == REGION_ATTN:
            return ATTN_CORE, which
        if comps[i] in REGIONS:
            return comps[i], which
    return None, which


def _region_of(op_name: str) -> Tuple[str, str, bool]:
    """(region key, op_type, attributed) for one instruction's op_name.

    Attributed regions come from user named scopes: the Executor's
    ``<op_type>.b<N>.i<M>`` encoding (innermost match wins — sub-block ops
    nest inside their control-flow op's scope), else a training step's
    ``REGIONS`` (``<region>.fwd`` / ``.remat`` / ``.bwd``, see
    ``step_region``), else any named_scope path the user planted (dygraph
    Layers push their layer names).  ``jit(...)`` components are jax
    function boundaries, not user scopes, and the final component is the
    lowered primitive — both are stripped."""
    if not op_name or "/" not in op_name:
        return ("<unattributed>", op_name or "<none>", False)
    comps = op_name.split("/")
    for comp in reversed(comps):
        core = _unwrap(comp)
        m = OP_SCOPE_RE.match(core)
        if m is not None:
            return (core, m.group(1), True)
    region, which = step_region(op_name)
    if region is not None:
        return (f"{region}.{which}", region, True)
    kept = []
    for comp in comps[:-1]:
        if comp.startswith(("jit(", "pjit(")):
            continue
        core = _unwrap(comp)
        if core.startswith(("jit(", "pjit(")) or not core:
            continue
        kept.append(core)
    if kept:
        return ("/".join(kept), kept[-1], True)
    return ("<unattributed>", _unwrap(comps[-1]), False)


class _Region:
    __slots__ = ("key", "op_type", "attributed", "flops", "bytes", "instrs")

    def __init__(self, key: str, op_type: str, attributed: bool):
        self.key = key
        self.op_type = op_type
        self.attributed = attributed
        self.flops = 0.0
        self.bytes = 0.0
        self.instrs = 0


def attribute_hlo(text: str) -> Dict[str, _Region]:
    """Walk every module's entry computation (recursing into fusion bodies,
    while bodies/conditions and conditional branches), model per-instruction
    flops and bytes, and aggregate per source region.

    Bytes are modeled at fusion granularity: instructions inside a fused
    computation contribute flops to their own region but no bytes (fused
    intermediates never touch HBM); the fusion instruction's operand +
    output traffic lands on the fusion root's region.  Loop bodies count
    once — HLO does not carry trip counts."""
    comps, entries = parse_hlo(text)
    regions: Dict[str, _Region] = {}
    visited = set()

    def reg(op_name: str) -> _Region:
        key, op_type, attributed = _region_of(op_name)
        r = regions.get(key)
        if r is None:
            r = regions[key] = _Region(key, op_type, attributed)
        return r

    def walk(comp_name: str, fused: bool) -> None:
        if comp_name in visited or comp_name not in comps:
            return
        visited.add(comp_name)
        for instr in comps[comp_name]:
            r = reg(instr.op_name)
            fl = _instr_flops(instr)
            if fl:
                r.flops += fl
            if instr.opcode == "fusion":
                if not fused:
                    r.bytes += _instr_bytes(instr)
                r.instrs += 1
                m = _CALLS_RE.search(instr.rest)
                if m is not None:
                    walk(m.group(1), True)
                continue
            if instr.opcode == "while":
                r.instrs += 1
                for pat in (_BODY_RE, _COND_RE):
                    m = pat.search(instr.rest)
                    if m is not None:
                        walk(m.group(1), fused)
                continue
            if instr.opcode == "conditional":
                r.instrs += 1
                m = _BRANCHES_RE.search(instr.rest)
                if m is not None:
                    for b in re.findall(r"%?([\w.\-]+)", m.group(1)):
                        walk(b, fused)
                continue
            if not fused:
                r.bytes += _instr_bytes(instr)
            r.instrs += 1

    for entry in entries:
        walk(entry, False)
    return regions


# ---------------------------------------------------------------------------
# Report assembly.
# ---------------------------------------------------------------------------
def _cost_dict(cost) -> Dict[str, float]:
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    return cost if isinstance(cost, dict) else {}


def build_report(hlo_text: str, cost=None, memory: Optional[dict] = None,
                 measured_ms: Optional[float] = None,
                 peaks: Optional[PeakSpec] = None,
                 top: Optional[int] = None) -> Dict[str, Any]:
    """The xprof report: per-region roofline over the attribution of
    ``hlo_text``, anchored by XLA's ``cost_analysis`` totals (``cost``) and
    a measured step time when available."""
    peaks = peaks or resolve_peaks()
    regions = attribute_hlo(hlo_text)
    total_flops = sum(r.flops for r in regions.values())
    total_bytes = sum(r.bytes for r in regions.values())
    attributed = sum(r.flops for r in regions.values() if r.attributed)
    coverage = (attributed / total_flops) if total_flops > 0 else 1.0

    rows = []
    for r in regions.values():
        t_c = r.flops / peaks.flops_per_sec
        t_m = r.bytes / peaks.bytes_per_sec
        t = max(t_c, t_m)
        ai = (r.flops / r.bytes) if r.bytes > 0 else math.inf
        rows.append({
            "region": r.key,
            "op_type": r.op_type,
            "attributed": r.attributed,
            "instructions": r.instrs,
            "flops": r.flops,
            "bytes": r.bytes,
            "arithmetic_intensity": (round(ai, 3) if math.isfinite(ai)
                                     else None),
            "bound": "compute" if t_c >= t_m else "memory",
            "modeled_ms": t * 1000.0,
            "mfu": (r.flops / (t * peaks.flops_per_sec)) if t > 0 else 0.0,
        })
    rows.sort(key=lambda row: row["modeled_ms"], reverse=True)
    modeled_ms = sum(row["modeled_ms"] for row in rows)
    for row in rows:
        row["share"] = (row["modeled_ms"] / modeled_ms) if modeled_ms > 0 \
            else 0.0
        row["modeled_ms"] = round(row["modeled_ms"], 6)
        row["share"] = round(row["share"], 4)
        row["mfu"] = round(row["mfu"], 4)
    if top is not None:
        dropped = len(rows) - int(top)
        rows = rows[:int(top)]
    else:
        dropped = 0

    by_type: Dict[str, Dict[str, float]] = {}
    for r in regions.values():
        agg = by_type.setdefault(
            r.op_type, {"flops": 0.0, "bytes": 0.0, "regions": 0})
        agg["flops"] += r.flops
        agg["bytes"] += r.bytes
        agg["regions"] += 1

    cd = _cost_dict(cost)
    flops_xla = cd.get("flops")
    bytes_xla = cd.get("bytes accessed")
    mfu_model = (total_flops / (modeled_ms / 1000.0 * peaks.flops_per_sec)
                 if modeled_ms > 0 else 0.0)
    mfu_meas = drift = None
    if measured_ms and measured_ms > 0:
        mfu_meas = total_flops / (measured_ms / 1000.0 * peaks.flops_per_sec)
        drift = measured_ms / modeled_ms if modeled_ms > 0 else None

    report = {
        "schema": "xprof.report.v1",
        "device": peaks.to_json(),
        "totals": {
            "flops_modeled": total_flops,
            "bytes_modeled": total_bytes,
            "flops_xla": flops_xla,
            "bytes_xla": bytes_xla,
            "attributed_flops": attributed,
            "attribution_coverage": round(coverage, 4),
            "modeled_ms": round(modeled_ms, 6),
            "measured_ms": (round(measured_ms, 4) if measured_ms else None),
            "measured_vs_modeled": (round(drift, 3) if drift else None),
            "mfu_modeled": round(mfu_model, 6),
            "mfu_measured": (round(mfu_meas, 6) if mfu_meas is not None
                             else None),
        },
        "regions": rows,
        "regions_dropped": max(0, dropped),
        "by_op_type": {k: {"flops": v["flops"], "bytes": v["bytes"],
                           "regions": int(v["regions"])}
                       for k, v in sorted(by_type.items())},
    }
    if memory:
        report["memory"] = memory
    _m_reports.inc()
    if _monitor.enabled():
        _m_coverage.set(report["totals"]["attribution_coverage"])
        _m_mfu.set(mfu_meas if mfu_meas is not None else mfu_model)
    _remember(report)
    return report


def memory_stats(aot) -> Optional[Dict[str, int]]:
    """Device-memory breakdown of a compiled executable via
    ``memory_analysis()``: argument / output / temp / generated-code bytes
    (None when the backend exposes no memory model)."""
    try:
        ma = aot.memory_analysis()
    except Exception:
        return None
    if isinstance(ma, (list, tuple)):
        ma = ma[0] if ma else None
    if ma is None:
        return None
    try:
        stats = {
            "args_bytes": int(ma.argument_size_in_bytes),
            "out_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "code_bytes": int(ma.generated_code_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
    except AttributeError:
        return None
    stats["total_bytes"] = (stats["args_bytes"] + stats["out_bytes"]
                            + stats["temp_bytes"] + stats["code_bytes"])
    return stats


def live_array_census() -> Dict[str, Any]:
    """What is actually resident: count and bytes of every live
    ``jax.Array`` in the process (committed or not)."""
    import jax

    count = 0
    nbytes = 0
    for a in jax.live_arrays():
        count += 1
        nbytes += getattr(a, "nbytes", 0) or 0
    return {"count": count, "bytes": nbytes}


def profile_aot(aot, measured_ms: Optional[float] = None,
                peaks: Optional[PeakSpec] = None,
                top: Optional[int] = None) -> Dict[str, Any]:
    """Build the report straight from a jax AOT-compiled executable
    (``jit(f).lower(...).compile()``): optimized HLO text + cost_analysis +
    memory_analysis, all from the artifact that actually runs."""
    text = aot.as_text()
    cost = None
    try:
        cost = aot.cost_analysis()
    except Exception:
        pass
    return build_report(text, cost=cost, memory=memory_stats(aot),
                        measured_ms=measured_ms, peaks=peaks, top=top)


def roofline_totals(aot) -> Optional[Dict[str, Any]]:
    """The roofline ``totals`` block straight off an AOT executable — the
    modeled-ms leg the calibration ledger (utils/ledger.py) joins against
    measured step time.  None when the backend yields no profile (e.g. a
    deserialized persistent-cache artifact without cost analysis)."""
    try:
        return profile_aot(aot)["totals"]
    except Exception:
        return None


def profile_jit(fn, *example, measured_ms: Optional[float] = None,
                peaks: Optional[PeakSpec] = None,
                top: Optional[int] = None) -> Dict[str, Any]:
    """Lower + compile ``fn`` against ``example`` args and profile the
    result.  ``fn`` may already be jitted; a plain callable is jitted."""
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    aot = jitted.lower(*example).compile()
    return profile_aot(aot, measured_ms=measured_ms, peaks=peaks, top=top)


# ---------------------------------------------------------------------------
# Rendering.
# ---------------------------------------------------------------------------
def _human(n: Optional[float]) -> str:
    if n is None:
        return "-"
    n = float(n)
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(n) >= div:
            return f"{n / div:.2f}{unit}"
    return f"{n:.0f}"


def render_table(report: Dict[str, Any], top: int = 20) -> str:
    """Human-readable report: totals header + ranked region table."""
    t = report["totals"]
    dev = report["device"]
    lines = [
        f"xprof report — device {dev['kind']} "
        f"(peak {_human(dev['peak_flops_per_sec'])}F/s, "
        f"{_human(dev['peak_bytes_per_sec'])}B/s, "
        f"ridge {dev['ridge_flops_per_byte']} F/B, {dev['source']})",
        f"  flops modeled {_human(t['flops_modeled'])} "
        f"(xla: {_human(t['flops_xla'])})   "
        f"bytes modeled {_human(t['bytes_modeled'])} "
        f"(xla: {_human(t['bytes_xla'])})",
        f"  attribution coverage {t['attribution_coverage']:.1%}   "
        f"modeled {t['modeled_ms']:.4f} ms   "
        f"measured {t['measured_ms'] if t['measured_ms'] is not None else '-'} ms"
        f"   drift x{t['measured_vs_modeled'] if t['measured_vs_modeled'] is not None else '-'}",
        f"  MFU modeled {t['mfu_modeled']:.4f}"
        + (f"   MFU measured {t['mfu_measured']:.4f}"
           if t["mfu_measured"] is not None else ""),
        "",
        f"{'region':<44} {'bound':<7} {'flops':>9} {'bytes':>9} "
        f"{'AI':>8} {'ms(model)':>10} {'share':>7} {'MFU':>7}",
    ]
    for row in report["regions"][:top]:
        ai = row["arithmetic_intensity"]
        lines.append(
            f"{row['region'][:44]:<44} {row['bound']:<7} "
            f"{_human(row['flops']):>9} {_human(row['bytes']):>9} "
            f"{(f'{ai:.1f}' if ai is not None else 'inf'):>8} "
            f"{row['modeled_ms']:>10.4f} {row['share']:>6.1%} "
            f"{row['mfu']:>7.3f}")
    hidden = len(report["regions"]) - top + report.get("regions_dropped", 0)
    if hidden > 0:
        lines.append(f"  ... {hidden} more regions (use --top/--format json)")
    if "memory" in report:
        m = report["memory"]
        lines.append(
            f"memory: args {_human(m['args_bytes'])}B  "
            f"out {_human(m['out_bytes'])}B  temp {_human(m['temp_bytes'])}B  "
            f"code {_human(m['code_bytes'])}B  "
            f"total {_human(m['total_bytes'])}B")
    return "\n".join(lines)


def summarize(report: Dict[str, Any], top: int = 3) -> Dict[str, Any]:
    """Condensed block for bench JSON lines and flight-recorder events:
    coverage, MFU, drift, and the top regions (plus the top memory-bound
    ones by name — the answer to "which regions are eating the step")."""
    t = report["totals"]
    return {
        "device": report["device"]["kind"],
        "attribution_coverage": t["attribution_coverage"],
        "mfu_modeled": t["mfu_modeled"],
        "mfu_measured": t["mfu_measured"],
        "measured_vs_modeled": t["measured_vs_modeled"],
        "top_regions": [
            {"region": r["region"], "bound": r["bound"],
             "modeled_ms": r["modeled_ms"], "share": r["share"]}
            for r in report["regions"][:top]],
        "top_memory_bound": [
            r["region"] for r in report["regions"]
            if r["bound"] == "memory"][:top],
        "memory": report.get("memory"),
    }


# ---------------------------------------------------------------------------
# Flight-recorder integration: the last summary rides post-mortem dumps.
# ---------------------------------------------------------------------------
_last_lock = threading.Lock()
_last_summary: Optional[Dict[str, Any]] = None
_hook_registered = False


def last_summary() -> Optional[Dict[str, Any]]:
    with _last_lock:
        return dict(_last_summary) if _last_summary is not None else None


def _remember(report: Dict[str, Any]) -> None:
    global _last_summary, _hook_registered
    s = summarize(report)
    s.pop("memory", None)  # keep the flight event compact
    with _last_lock:
        _last_summary = s
        if not _hook_registered:
            _hook_registered = True
            _trace.register_postmortem_info("xprof.summary", last_summary)


if __name__ == "__main__":  # pragma: no cover - convenience passthrough
    import sys

    from tools import xprof as _cli

    sys.exit(_cli.main())

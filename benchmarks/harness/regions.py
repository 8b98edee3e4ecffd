"""Device time by region of the compiled step.

The program plants `jax.named_scope`s where it builds the work (`embed`,
`encoder`, `attn`, `attn/core`, `ffn`, `ln`, `head`, `loss`, `optimizer`).
On the chip a scope does not reach the profiler's events as this harness
reads them: an event carries the instruction's own name (`fusion.559`,
`flash_packed_fwd.3`) and no metadata.  The compiled step's text does:
every instruction there has `metadata={op_name="jit(train_step)/..."}`.
This module joins the two: from the text, a region for every instruction
name; from the trace, each leaf operation's device time into one bucket.

The yardstick's own: it imports nothing of the program (the entry the
configuration names builds the step, as in `runner.run`), and the region
names below are this file's copy of the program's.  A program without them
(the parent of the PR that brought them) reads as nothing, not as zero.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import json
import re
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

from . import trafficgen, weights, xplane

ENCODER, CORE = "encoder", "attn/core"
SCOPES = ("embed", ENCODER, "attn", CORE, "ffn", "ln", "head", "loss",
          "optimizer")
# what a leaf operation's time can land in: every scope but `encoder`, whose
# own remainder (under `encoder`, in no finer scope: the scan stacking and
# slicing its residuals) is `scan`; collectives whatever their scope; the rest
SCAN, COLLECTIVE, UNSCOPED = "scan", "collective", "unscoped"
BUCKETS = tuple(s for s in SCOPES if s != ENCODER) + (SCAN, COLLECTIVE,
                                                     UNSCOPED)
MIN_COVERAGE = 0.99

# ---------------------------------------------------------------------------
# (a) the text: {instruction name: (region, pass, how it was resolved)}
# ---------------------------------------------------------------------------
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.+\{\s*$")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
    r"(?:\(.*?\)|[a-z0-9]+\[[0-9,\s]*\]\S*)\s+([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_JIT = re.compile(r"\bp?jit\([^()]*\)")
_WRAPPERS = re.compile(r"[\w.\-]+\(|\)")
_PRODUCTS = ("convolution", "dot")

_REF = re.compile(r"%([\w.\-]+)")

Instr = collections.namedtuple("Instr", "name opcode op_name calls operands")


def region_of(op_name: str) -> Tuple[Optional[str], str]:
    """(innermost known scope or None, "fwd" | "bwd") of an `op_name` path.
    `jit(train_step)/transpose(jvp(encoder))/while/body/closed_call/ffn/mul`
    is ("ffn", "bwd"): wrappers of transformations are peeled off, a path
    that went through `transpose(` is the backward of its scope (a
    checkpoint's recomputed forward included), and `core` directly under
    `attn` is `attn/core`."""
    which = "bwd" if "transpose(" in op_name else "fwd"
    comps = _WRAPPERS.sub("", _JIT.sub("", op_name)).split("/")
    for i in range(len(comps) - 1, -1, -1):
        if comps[i] == "core" and i and comps[i - 1] == "attn":
            return CORE, which
        if comps[i] in SCOPES:
            return comps[i], which
    return None, which


def parse(text: str) -> Dict[str, List[Instr]]:
    """{computation: its instructions} of optimized HLO text, with or
    without shapes on the operands."""
    comps, current = {}, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            current = comps.setdefault(m.group(1), [])
            continue
        if current is None:
            continue
        if line.strip() == "}":
            current = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        op_name, calls = _OP_NAME.search(line), _CALLS.search(line)
        refs = _REF.findall(line[m.end():].split(", metadata=")[0])
        current.append(Instr(m.group(1), m.group(2),
                             op_name.group(1) if op_name else "",
                             calls.group(1) if calls else None, refs))
    return comps


def _inside(comps, name, seen=None) -> List[Instr]:
    """Every instruction of a called computation, nested calls included."""
    seen = set() if seen is None else seen
    if name in seen or name not in comps:
        return []
    seen.add(name)
    out = []
    for ins in comps[name]:
        out.append(ins)
        if ins.calls:
            out += _inside(comps, ins.calls, seen)
    return out


def _finer(instrs: Sequence[Instr]):
    """The most frequent (region, pass) among `instrs` that is finer than
    `encoder`, or None."""
    found = collections.Counter(
        r for r in (region_of(i.op_name) for i in instrs if i.op_name)
        if r[0] not in (None, ENCODER))
    return found.most_common(1)[0][0] if found else None


def instruction_regions(text: str) -> Dict[str, Tuple[Optional[str], str, str]]:
    """{instruction name: (region, pass, how)} for every instruction of the
    module.  region: the innermost known scope of the instruction's own
    `op_name` ("own").  A fusion whose own path names nothing finer than
    `encoder` (XLA names a fusion after its root: a `dynamic-update-slice`
    that stacks the scan's residual, say) takes the region of the
    `convolution`/`dot` inside its called computation where there is one
    ("inner product"), else the most frequent finer region among its
    instructions ("inner majority").  An instruction that the compiler made
    and gave no `op_name` at all (a prefetch's `copy-start`/`copy-done`, a
    `slice-done`, the `ConcatBitcast` over them) does its work for the
    instruction that uses it, and takes the region of its first user that
    has one ("user")."""
    comps = parse(text)
    out = {}
    for instrs in comps.values():
        bare = []
        for ins in instrs:
            region, which = region_of(ins.op_name)
            how = "own"
            if region in (None, ENCODER) and ins.calls:
                inner = _inside(comps, ins.calls)
                for via, pool in (
                        ("inner product",
                         [i for i in inner if i.opcode in _PRODUCTS]),
                        ("inner majority", inner)):
                    found = _finer(pool)
                    if found is not None:
                        (region, which), how = found, via
                        break
            out[ins.name] = (region, which, how)
            if not ins.op_name and region is None:
                bare.append(ins.name)
        if bare:
            users = collections.defaultdict(list)
            for ins in instrs:
                for ref in ins.operands:
                    users[ref].append(ins.name)
            for name in reversed(bare):     # a user comes after its operand
                found = next((out[u] for u in users[name]
                              if out[u][0] is not None), None)
                if found is not None:
                    out[name] = (found[0], found[1], "user")
    return out


_PATHS = re.compile(r'op_name="([^"]*)"|loc\("([^"]*/[^"]*)"\(')


def has_scopes(text: str) -> bool:
    """Does any `op_name` of compiled text, or any name path in a `loc` of
    a lowered module's text, go through one of the scopes?  A program
    without them has nothing to read."""
    return any(region_of(a or b)[0] is not None
               for a, b in _PATHS.findall(text))


# ---------------------------------------------------------------------------
# (b), (c) the join with the trace
# ---------------------------------------------------------------------------
def bucket_of(event_name: str, regions: dict) -> Optional[str]:
    """The one bucket of an event; None where the text has no such name."""
    if xplane.COLLECTIVE.match(event_name):
        return COLLECTIVE
    if event_name not in regions:
        return None
    region = regions[event_name][0]
    return UNSCOPED if region is None else SCAN if region == ENCODER else region


def attribute(w: xplane.DeviceWindow, regions: dict) -> dict:
    """One chip's busy time, every nanosecond in exactly one place:
    {"ns": {(bucket, pass): ns}, "events": {(bucket, pass): count},
     "ops": {(bucket, pass): {name: ns}}, "busy_ns", "coverage",
     "missing": {name: ns}, "steps", "chip"}.  An operation's time is its
    own interval less what an operation that started earlier already
    covers, so the buckets add up to the busy time (`xplane`'s union of the
    leaves).  An event whose name the text does not have goes to `unscoped`
    and lowers `coverage`, the share of the busy time whose names were
    found."""
    ns, events = collections.Counter(), collections.Counter()
    ops = collections.defaultdict(collections.Counter)
    missing, covered = collections.Counter(), w.lo
    for e in sorted(xplane.leaves(w.ops), key=lambda e: (e.start, e.end)):
        own = max(0.0, e.end - max(e.start, covered))
        covered = max(covered, e.end)
        bucket = bucket_of(e.name, regions)
        if bucket is None:
            missing[e.name] += own
            bucket = UNSCOPED
        key = (bucket, regions.get(e.name, (None, "fwd"))[1])
        ns[key] += own
        events[key] += 1
        ops[key][e.name] += own
    busy = xplane.total(w.busy)
    return {"ns": dict(ns), "events": dict(events),
            "ops": {k: dict(v) for k, v in ops.items()}, "busy_ns": busy,
            "coverage": 1.0 - sum(missing.values()) / busy if busy else 0.0,
            "missing": dict(missing), "steps": w.steps, "chip": w.name}


def table(ws: Sequence[xplane.DeviceWindow], regions: dict) -> dict:
    """The attribution of the slowest chip: the one busy longest."""
    return attribute(max(ws, key=lambda w: xplane.total(w.busy)), regions)


def ms_per_step(t: dict, buckets: Sequence[str]) -> float:
    return sum(v for (b, _), v in t["ns"].items() if b in buckets) \
        / t["steps"] / 1e6


# ---------------------------------------------------------------------------
# getting the text: the step rebuilt as `runner.run` builds it
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def _metadata_in_cache_key():
    """JAX's persistent cache keys a program without its metadata, so a
    cache shared with a checkout whose program has other scopes (or none)
    can serve that checkout's executable, text included: the same
    instructions under the same names, mapped to the other program's
    regions.  With the metadata in the key the text is this program's own;
    the first traced run of a cell compiles for it, the next ones hit."""
    import jax
    name = "jax_compilation_cache_include_metadata_in_key"
    old = getattr(jax.config, name)
    jax.config.update(name, True)
    try:
        yield
    finally:
        jax.config.update(name, old)


_texts: Dict[str, Tuple[Optional[str], Optional[str]]] = {}


def step_text(manifest, model: dict, mix: dict, chips: int,
              err=None) -> Tuple[Optional[str], Optional[str]]:
    """(the optimized HLO text of the cell's compiled step, or None where
    the program has no region scopes; the step's program as a trace names
    it), made once per process.  Finds the configuration by its `model`,
    builds the entry and its state as `runner.run` does, lowers the step
    over them and compiles it under a cache key of its own
    (`_metadata_in_cache_key`)."""
    memo = json.dumps([str(manifest.path), model, mix, chips], sort_keys=True)
    if memo not in _texts:
        _texts[memo] = _step_text(manifest, model, mix, chips,
                                  err or sys.stderr)
    return _texts[memo]


def _step_text(manifest, model, mix, chips, err):
    import jax
    config = next((c for c in (manifest.config(e["name"])
                               for e in manifest.data["configs"])
                   if c["model"] == model), None)
    if config is None:
        print("regions: no configuration has this model", file=err)
        return None, None
    t0 = time.perf_counter()
    entry = manifest.module("entries", config["entry"])
    reference = manifest.module("references", config["reference"])
    spec = reference.param_spec(config["model"])
    training = entry.build(config, mix, jax.devices()[:chips])

    # the run's own objects, made the same way (an abstract stand-in would
    # have to guess which leaves of the optimizer's state are committed to
    # a device); the run's state is freed by now, so there is room
    params = weights.maker(spec, training.param_shardings(
        weights.shapes(spec)))(weights.seed_key(0))
    opt_state = training.init_opt_state(params)
    batch = jax.device_put(
        trafficgen.make_pool(dict(mix, pool=1), config["model"], 0)[0],
        training.data_shardings)
    lowered = training.step.lower(params, opt_state, batch, training.key)
    text = None
    if not has_scopes(lowered.as_text(debug_info=True)):
        print("regions: the program's step has no region scopes", file=err)
    else:
        with _metadata_in_cache_key():
            text = lowered.compile().as_text()
        print(f"regions: the step rebuilt and its text taken in "
              f"{time.perf_counter() - t0:.1f} s", file=err)
    step_module = training.step_module
    del training, params, opt_state, batch, lowered
    gc.collect()
    return text, step_module


def of(ctx: dict, err=None) -> Optional[dict]:
    """The run's region table (`table`), made once per run and kept in
    `ctx`; None, with the reason on stderr, where there is no trace, the
    program has no scopes, or the rebuilt step's names cover under 99% of
    the traced device time (never a number from a wrong map)."""
    if "regions" not in ctx:
        ctx["regions"], err = None, err or sys.stderr
        if ctx.get("trace"):
            text, _ = step_text(ctx["manifest"], ctx["model"], ctx["mix"],
                                ctx["chips"], err)
            if text is not None:
                t = table(ctx["trace"], instruction_regions(text))
                if t["coverage"] < MIN_COVERAGE:
                    worst = sorted(t["missing"].items(),
                                   key=lambda kv: -kv[1])[:5]
                    print(f"regions: the rebuilt step's names cover "
                          f"{100 * t['coverage']:.2f}% of the traced device "
                          f"time, under {100 * MIN_COVERAGE:.0f}%: not "
                          f"read; missing most: {worst}", file=err)
                else:
                    print(f"regions: coverage {100 * t['coverage']:.3f}% on "
                          f"{t['chip']}", file=err)
                    ctx["regions"] = t
    return ctx["regions"]

import collections

from benchmarks.harness import regions

_PRODUCTS = ("convolution", "dot")


def subscope_of(op_name: str, region: str, subs) -> str:
    """The innermost of `subs` that an `op_name` path holds after `region`
    (`.../ffn/experts/dot_general` -> "experts"), or None."""
    comps = regions._WRAPPERS.sub("", regions._JIT.sub("", op_name)).split("/")
    if region not in comps:
        return None
    start = len(comps) - 1 - comps[::-1].index(region)
    return next((c for c in reversed(comps[start + 1:]) if c in subs), None)


def instruction_subscopes(text: str, region: str, subs) -> dict:
    """{instruction name: sub-scope or None}, resolved as
    `regions.instruction_regions` resolves a region: the instruction's own
    path; for a fusion whose own path names none, its inner products', else
    its instructions' most frequent."""
    comps = regions.parse(text)
    out = {}
    for instrs in comps.values():
        for ins in instrs:
            sub = subscope_of(ins.op_name, region, subs)
            if sub is None and ins.calls:
                inner = regions._inside(comps, ins.calls)
                for pool in ([i for i in inner if i.opcode in _PRODUCTS],
                             inner):
                    found = collections.Counter(
                        s for s in (subscope_of(i.op_name, region, subs)
                                    for i in pool) if s)
                    if found:
                        sub = found.most_common(1)[0][0]
                        break
            out[ins.name] = sub
    return out


def ms_per_step(ctx, region: str, sub: str, subs):
    """Device time per step, in ms, of the operations that the region table
    (`regions.of`: the run's trace joined with the compiled step's text,
    coverage of 99% or nothing) gives to `region` and whose instruction lies
    under `<region>/<sub>`; None where there is no table or no such
    operation ran."""
    t = regions.of(ctx)
    if t is None:
        return None
    text, _ = regions.step_text(ctx["manifest"], ctx["model"], ctx["mix"],
                                ctx["chips"])
    where = ctx.setdefault("subscopes", {}).get((region, tuple(subs)))
    if where is None:
        where = ctx["subscopes"][(region, tuple(subs))] = \
            instruction_subscopes(text, region, subs)
    ns = sum(own for (bucket, _), ops in t["ops"].items() if bucket == region
             for name, own in ops.items() if where.get(name) == sub)
    return ns / t["steps"] / 1e6 or None


def read(ctx, params):
    """Device time per step of one sub-scope of a region (`params`: region,
    sub, and subs, the sub-scopes the region has), forward and backward
    together, on the chip that is busy longest; never 0."""
    return ms_per_step(ctx, params["region"], params["sub"], params["subs"])

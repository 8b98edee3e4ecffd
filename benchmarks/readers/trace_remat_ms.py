import sys
import time

from benchmarks.harness import regions

MARK = "rematted_computation"


def recomputed(op_name: str) -> bool:
    """Does an `op_name` path go through a `jax.checkpoint`'s recomputed
    forward?  JAX marks it itself: the path holds the component
    `rematted_computation` (`.../checkpoint/rematted_computation/attn/...`;
    the checkpoint's backward runs under `.../checkpoint/...` alone).  One
    checkpoint inside another's recomputed forward holds it twice: still one
    recomputed instruction."""
    return MARK in regions._WRAPPERS.sub(
        "", regions._JIT.sub("", op_name)).split("/")


def instruction_remat(text: str) -> dict:
    """{instruction name: (recomputed forward?, how it was resolved, does it
    mix recomputed with other work?)} for every instruction of the module,
    resolved as `regions.instruction_regions` resolves a region: by the
    instruction's own path where that holds the mark ("own"); for a fusion
    whose own path does not (XLA names a fusion after its root, and fuses a
    recomputed product with the backward pass that reads it), by the paths
    of its inner `convolution`/`dot`s ("inner product"), else by those of
    more than half of its instructions that have a path ("inner majority");
    an instruction the compiler made and gave no path (a prefetch's
    `copy-start`), by its first user ("user").  A fusion mixes where the
    instructions inside it are of both kinds: it goes whole to one side,
    and a tool can say how much time that is."""
    comps = regions.parse(text)
    out = {}
    for instrs in comps.values():
        bare = []
        for ins in instrs:
            is_remat, how, mixed = recomputed(ins.op_name), "own", False
            if ins.calls:
                inner = [i for i in regions._inside(comps, ins.calls)
                         if "/" in i.op_name]
                marks = [recomputed(i.op_name) for i in inner]
                mixed = any(marks) and not all(marks)
                if any(marks) and not is_remat:
                    products = [recomputed(i.op_name) for i in inner
                                if i.opcode in regions._PRODUCTS]
                    how, pool = ("inner product", products) if products \
                        else ("inner majority", marks)
                    is_remat = 2 * sum(pool) > len(pool)
            out[ins.name] = (is_remat, how, mixed)
            if not ins.op_name and not ins.calls:
                bare.append(ins.name)
        if bare:    # made by the compiler for its user: the user's verdict
            users = {}
            for ins in instrs:
                for ref in ins.operands:
                    users.setdefault(ref, ins.name)
            for name in reversed(bare):     # a user comes after its operand
                if name in users:
                    out[name] = (out[users[name]][0], "user", False)
    return out


def of(ctx):
    """`instruction_remat` of the run's compiled step, parsed once a run and
    kept in `ctx`; the text is the one `regions.of` already took
    (`regions.step_text` is memoised: nothing is rebuilt or compiled)."""
    if "remat" not in ctx:
        t0 = time.perf_counter()
        text, _ = regions.step_text(ctx["manifest"], ctx["model"], ctx["mix"],
                                    ctx["chips"])
        ctx["remat"] = instruction_remat(text)
        print(f"remat: the step's text read in "
              f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
    return ctx["remat"]


def read(ctx, params):
    """Device time per step, in ms, of the recomputed forward: the
    operations of the region table (`regions.of`: the run's trace joined
    with the compiled step's text, coverage of 99% or nothing) whose
    instruction `instruction_remat` finds recomputed, on the chip that is
    busy longest; with `params["regions"]`, those of these buckets alone.
    None where there is no table or nothing was recomputed; never 0."""
    t = regions.of(ctx)
    if t is None:
        return None
    where, buckets = of(ctx), params.get("regions")
    ns = sum(own for (bucket, _), ops in t["ops"].items()
             if buckets is None or bucket in buckets
             for name, own in ops.items()
             if where.get(name, (False,))[0])
    return ns / t["steps"] / 1e6 or None

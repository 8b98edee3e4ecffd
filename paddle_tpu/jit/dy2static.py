"""Dygraph-to-static AST conversion for data-dependent Python control flow.

Reference parity: the dygraph_to_static transformer pipeline —
`ProgramTranslator` (fluid/dygraph/dygraph_to_static/program_translator.py:667)
with its per-construct transformers (ifelse_transformer.py,
loop_transformer.py) and the `convert_ifelse`/`convert_while_loop` runtime
dispatchers (convert_operators.py), which let `@to_static` code keep Python
`if`/`while` over tensors.

TPU-native design: most dygraph code traces directly under jax.jit, so the
AST pass only needs to rewrite the two constructs tracing cannot express —
`if` and `while` whose predicate is a *traced* value — into runtime
dispatchers that pick `lax.cond` / `lax.while_loop` when the predicate is a
tensor and plain Python control flow otherwise (exactly the reference's
convert_* contract).  Supported subset (documented, checked):

  * `if`/`elif`/`else` where every name live after the branch is assigned
    in BOTH branches (lax.cond needs matching output structures),
  * `while` whose carried names exist before the loop and keep
    shape/dtype (lax.while_loop shape-invariant carry),
  * `break`/`continue` inside `while`/`for` bodies (ref
    break_continue_transformer.py): rewritten into carried boolean flags
    — the loop condition gains AND NOT(break_flag), and statements after
    a potential break/continue are wrapped in guard `if`s, so a traced
    break predicate lowers to lax control flow,
  * `for i in range(...)` (ref loop_transformer.py for-range): lowered to
    the `while` form — static bounds keep the plain Python loop (list
    appends etc. still work), traced bounds or a traced break become
    lax.while_loop,
  * tail transformers (ref assert_transformer.py, cast_transformer.py,
    print_transformer.py, tensor_shape_transformer.py, convert_len):
    `assert` dispatches to a host check when the predicate is traced;
    `int(x)`/`float(x)`/`bool(x)` on traced tensors become astype;
    `print(tensor)` becomes jax.debug.print under trace; `len(tensor)`
    and `x.shape[i]` are STATIC under XLA, so the reference's
    dynamic-shape plumbing collapses to python ints (python lists with
    static-bound loops keep working through the plain-loop path for the
    same reason — the reference's LoDTensorArray conversion is only
    needed when shapes are dynamic),
  * no `return`/`yield` inside converted bodies; no list append inside a
    loop that actually lowers to lax.while_loop (a lax carry cannot grow
    — use a preallocated buffer + indexed writes, the dense analogue of
    the reference's LoDTensorArray); no closures over mutated free
    variables.

Functions using constructs outside the subset fall back to plain tracing
(data-INdependent control flow still works there); a data-dependent
predicate will then raise jax's TracerBoolConversionError as before.
"""
from __future__ import annotations

import ast
import functools
import inspect
import textwrap
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = ["ast_transform", "convert_ifelse", "convert_while",
           "convert_assert", "convert_cast", "convert_print", "convert_len",
           "Unsupported"]


class Unsupported(Exception):
    """Raised when a function is outside the convertible subset."""


_UNDEF = object()  # placeholder for names not yet bound before an `if`


def _is_traced(x) -> bool:
    return isinstance(x, (jax.core.Tracer, jax.Array))


def convert_ifelse(pred, true_fn: Callable, false_fn: Callable,
                   args: Tuple) -> Tuple:
    """ref convert_operators.py convert_ifelse: tensor pred -> lax.cond,
    python pred -> plain call."""
    if _is_traced(pred):
        p = jnp.reshape(pred, ()).astype(bool)
        out_t = true_fn(*args)
        out_f = false_fn(*args)
        _check_match(out_t, out_f)
        # names unbound before the `if` (fresh in both branches) carry a
        # placeholder; lax.cond operands must be arrays, so substitute a
        # dummy — the branches provably assign before use (checked above)
        safe = tuple(jnp.zeros(()) if a is _UNDEF else a for a in args)
        return jax.lax.cond(p, lambda a: true_fn(*a), lambda a: false_fn(*a),
                            safe)
    return true_fn(*args) if pred else false_fn(*args)


def _check_match(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    for x, y in zip(fa, fb):
        xs = getattr(x, "shape", ()) if x is not _UNDEF else None
        ys = getattr(y, "shape", ()) if y is not _UNDEF else None
        if x is _UNDEF or y is _UNDEF or xs != ys:
            raise Unsupported(
                "converted `if`: both branches must assign every output "
                f"with matching shapes (got {xs} vs {ys}); a name assigned "
                "in only one branch cannot cross a lax.cond boundary")


def convert_while(cond_fn: Callable, body_fn: Callable, carry: Tuple) -> Tuple:
    """ref convert_operators.py convert_while_loop."""
    probe = cond_fn(*carry)
    if _is_traced(probe):
        if any(c is _UNDEF for c in carry):
            raise Unsupported(
                "converted `while`: every carried variable must be bound "
                "before the loop (lax.while_loop carry)")
        # flags introduced by the break/continue rewrite start as python
        # bools; canonicalize the carry so the while_loop typechecks
        carry = tuple(jnp.asarray(c) if isinstance(c, (bool, int, float))
                      else c for c in carry)
        return jax.lax.while_loop(
            lambda c: jnp.reshape(cond_fn(*c), ()).astype(bool),
            lambda c: tuple(body_fn(*c)), tuple(carry))
    while True:
        if _is_traced(probe):
            # the condition became traced mid-flight (e.g. a traced break
            # flag joined it): continue as lax.while_loop from here
            return convert_while(cond_fn, body_fn, carry)
        if not probe:
            return carry
        carry = tuple(body_fn(*carry))
        probe = cond_fn(*carry)


def convert_assert(pred, msg_fn=None):
    """ref dygraph_to_static/assert_transformer.py -> layers.Assert: a
    traced predicate checks host-side via ordered io_callback;
    concrete values keep plain `assert` semantics.  ``msg_fn`` is a THUNK:
    python evaluates an assert's message only on failure, so the AST
    rewrite wraps it in a lambda and it is called here only when the
    check actually fails."""
    def _msg():
        return msg_fn() if msg_fn is not None else "converted assert failed"

    if isinstance(pred, jax.core.Tracer):
        import numpy as np
        from jax.experimental import io_callback

        def host_check(p):
            # ALL elements must hold (the reference Assert op contract;
            # eager python would refuse a multi-element truth test)
            if not bool(np.asarray(p).all()):
                raise AssertionError(_msg())
            return np.zeros((), np.int32)

        io_callback(host_check, jax.ShapeDtypeStruct((), jnp.int32),
                    pred, ordered=True)
        return
    if not pred:
        raise AssertionError(_msg())


_CAST_DTYPES = {"int": jnp.int32, "float": jnp.float32, "bool": jnp.bool_}


def convert_cast(value, ty: str):
    """ref cast_transformer.py: int(x)/float(x)/bool(x) on a TRACED tensor
    become astype (int32/float32/bool — x64 is off on TPU); concrete
    values keep the builtin conversion."""
    if isinstance(value, jax.core.Tracer):
        return value.astype(_CAST_DTYPES[ty])
    return {"int": int, "float": float, "bool": bool}[ty](value)


def convert_print(*args, **kwargs):
    """ref print_transformer.py -> Print op: traced args print host-side
    via ordered io_callback with FULL builtin-print semantics (sep/end/
    file honored — jax.debug.print would drop them); same runtime caveat
    as convert_assert.  Concrete values use builtin print directly."""
    if any(isinstance(a, jax.core.Tracer) for a in args):
        import numpy as np
        from jax.experimental import io_callback

        arr_idx = [i for i, a in enumerate(args)
                   if isinstance(a, (jax.core.Tracer, jax.Array))]
        static_args = list(args)

        def host_print(*arrs):
            merged = list(static_args)
            for i, a in zip(arr_idx, arrs):
                merged[i] = np.asarray(a)
            print(*merged, **kwargs)
            return np.zeros((), np.int32)

        io_callback(host_print, jax.ShapeDtypeStruct((), jnp.int32),
                    *[args[i] for i in arr_idx], ordered=True)
        return
    print(*args, **kwargs)


def convert_len(x):
    """ref convert_operators.py convert_len + tensor_shape_transformer:
    len(tensor) is the leading dim — STATIC under XLA, so the reference's
    dynamic-shape plumbing collapses to a python int."""
    if isinstance(x, (jax.core.Tracer, jax.Array)):
        return x.shape[0]
    return len(x)


def _and_not(test, brk):
    """cond AND NOT break_flag, python/tensor aware (break rewrite)."""
    if _is_traced(test) or _is_traced(brk):
        t = jnp.reshape(jnp.asarray(test), ()).astype(bool)
        b = jnp.reshape(jnp.asarray(brk), ()).astype(bool)
        return jnp.logical_and(t, jnp.logical_not(b))
    return bool(test) and not bool(brk)


def _not_skipping(brk, cnt):
    """NOT (break_flag OR continue_flag) — the guard predicate wrapping
    statements after a potential break/continue."""
    if _is_traced(brk) or _is_traced(cnt):
        b = jnp.reshape(jnp.asarray(brk), ()).astype(bool)
        c = jnp.reshape(jnp.asarray(cnt), ()).astype(bool)
        return jnp.logical_not(jnp.logical_or(b, c))
    return not (bool(brk) or bool(cnt))


def _range_cond(i, stop, step):
    """for-range continuation predicate, sign-of-step aware."""
    if _is_traced(i) or _is_traced(stop) or _is_traced(step):
        return jnp.where(jnp.asarray(step) > 0,
                         jnp.asarray(i) < jnp.asarray(stop),
                         jnp.asarray(i) > jnp.asarray(stop))
    return i < stop if step > 0 else i > stop


# ------------------------------------------------------------------ AST ----

def _assigned_names(nodes: Sequence[ast.stmt]) -> list:
    names = []

    class V(ast.NodeVisitor):
        def visit_Name(self, n):
            if isinstance(n.ctx, ast.Store) and n.id not in names:
                names.append(n.id)

        def visit_FunctionDef(self, n):  # don't descend into nested defs
            pass

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_AugAssign(self, n):
            if isinstance(n.target, ast.Name) and n.target.id not in names:
                names.append(n.target.id)
            self.generic_visit(n)

    for s in nodes:
        V().visit(s)
    return names


class _Checker(ast.NodeVisitor):
    """Reject constructs the subset cannot express inside converted bodies:
    return/yield ANYWHERE (a generated body_fn must return the carry
    tuple — even inside a nested python-iterated `for` the return would
    escape the carry), break/continue only OUTSIDE nested loops (a nested
    loop owns its own, handled by its own conversion)."""

    def __init__(self):
        self.banned = None
        self.saw_bc = False  # break/continue at the CURRENT loop level
        self._loop_depth = 0

    def visit_Break(self, n):
        if self._loop_depth == 0:
            self.banned = "break"
            self.saw_bc = True

    def visit_Continue(self, n):
        if self._loop_depth == 0:
            self.banned = "continue"
            self.saw_bc = True

    def visit_Return(self, n):
        self.banned = "return"

    def visit_Yield(self, n):
        self.banned = "yield"

    def visit_FunctionDef(self, n):
        # nested defs (incl. ones this transformer generated for an inner
        # converted construct) own their returns — don't descend
        pass

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_Lambda = visit_FunctionDef

    def visit_While(self, n):
        self._loop_depth += 1
        self.generic_visit(n)
        self._loop_depth -= 1

    visit_For = visit_While


def _contains_bc(node: ast.stmt) -> bool:
    """Does this statement contain a break/continue belonging to the
    CURRENT loop (not to a nested loop)?"""
    c = _Checker()
    c.visit(node)
    return c.saw_bc


def _name(n, ctx=ast.Load):
    return ast.Name(id=n, ctx=ctx())


def _rewrite_break_continue(body, brk, cnt):
    """ref break_continue_transformer.py: replace break/continue with flag
    assignments and wrap the statements after a potential break/continue in
    a guard `if not (brk or cnt)` — which the If conversion then lowers to
    lax.cond when the flags are traced."""

    def rewrite_stmt(s):
        if isinstance(s, ast.Break):
            return [ast.Assign(targets=[_name(brk, ast.Store)],
                               value=ast.Constant(value=True))]
        if isinstance(s, ast.Continue):
            return [ast.Assign(targets=[_name(cnt, ast.Store)],
                               value=ast.Constant(value=True))]
        if isinstance(s, ast.If):
            s = ast.If(test=s.test, body=rewrite_block(s.body),
                       orelse=rewrite_block(s.orelse))
        return [s]

    def rewrite_block(stmts):
        out = []
        for i, s in enumerate(stmts):
            had_bc = _contains_bc(s)
            out.extend(rewrite_stmt(s))
            if had_bc and i + 1 < len(stmts):
                guard = ast.If(
                    test=ast.Call(
                        func=_name("__pdtpu_not_skipping"),
                        args=[_name(brk), _name(cnt)], keywords=[]),
                    body=rewrite_block(stmts[i + 1:]), orelse=[])
                out.append(guard)
                break
        return out

    return rewrite_block(body)


def _check_body(nodes):
    c = _Checker()
    for s in nodes:
        c.visit(s)
    if c.banned:
        raise Unsupported(
            f"`{c.banned}` inside a converted control-flow body is outside "
            "the dy2static subset")


_REWRITABLE_BUILTINS = ("print", "int", "float", "bool", "len")


def _shadowed_builtins(fdef) -> frozenset:
    """Rewritable builtin names the function rebinds — via params,
    assignments, for/with targets, imports, or nested definitions.  A call
    through a rebound name is the user's object, not the builtin, so the
    cast/print/len rewrite must not fire on it.  Collection is
    whole-function conservative: python scoping makes a name assigned
    anywhere in a scope local everywhere in it, and nested defs are folded
    in too (the transformer rewrites inside them as well)."""
    bound = set()
    for n in ast.walk(fdef):
        if isinstance(n, ast.Name) and isinstance(n.ctx, (ast.Store, ast.Del)):
            bound.add(n.id)
        elif isinstance(n, ast.arg):
            bound.add(n.arg)
        elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)) and n is not fdef:
            bound.add(n.name)
        elif isinstance(n, (ast.Import, ast.ImportFrom)):
            for alias in n.names:
                bound.add((alias.asname or alias.name).split(".")[0])
    return frozenset(bound & set(_REWRITABLE_BUILTINS))


class _Transformer(ast.NodeTransformer):
    def __init__(self, shadowed=()):
        self.counter = 0
        self.shadowed = frozenset(shadowed)

    def _fresh(self, kind):
        self.counter += 1
        return f"__pdtpu_{kind}_{self.counter}"

    # -- tail transformers: assert / cast / print / len ----------------------
    def visit_Assert(self, node: ast.Assert):
        self.generic_visit(node)
        # the message becomes a thunk: python evaluates it only on failure
        msg = (ast.Lambda(
            args=ast.arguments(posonlyargs=[], args=[], vararg=None,
                               kwonlyargs=[], kw_defaults=[], kwarg=None,
                               defaults=[]),
            body=node.msg) if node.msg is not None
            else ast.Constant(value=None))
        return ast.Expr(value=ast.Call(
            func=_name("__pdtpu_convert_assert"),
            args=[node.test, msg], keywords=[]))

    def visit_Call(self, node: ast.Call):
        self.generic_visit(node)
        if not isinstance(node.func, ast.Name):
            return node
        fid = node.func.id
        if fid in self.shadowed:  # user rebound the name; not the builtin
            return node
        if fid in ("int", "float", "bool") and len(node.args) == 1 \
                and not node.keywords:
            return ast.Call(func=_name("__pdtpu_convert_cast"),
                            args=[node.args[0], ast.Constant(value=fid)],
                            keywords=[])
        if fid == "len" and len(node.args) == 1 and not node.keywords:
            return ast.Call(func=_name("__pdtpu_convert_len"),
                            args=list(node.args), keywords=[])
        if fid == "print":
            return ast.Call(func=_name("__pdtpu_convert_print"),
                            args=list(node.args),
                            keywords=list(node.keywords))
        return node

    # -- if ------------------------------------------------------------------
    def visit_If(self, node: ast.If):
        self.generic_visit(node)
        outs = sorted(set(_assigned_names(node.body))
                      | set(_assigned_names(node.orelse)))
        if not outs:
            # pure side-effect-free branch on possibly-traced pred is
            # meaningless; leave python semantics (will raise if traced)
            return node
        _check_body(node.body)
        _check_body(node.orelse)
        tname, fname = self._fresh("true"), self._fresh("false")
        args = [ast.arg(arg=n) for n in outs]

        def mk(nm, body):
            stmts = list(body) or [ast.Pass()]
            stmts.append(ast.Return(value=ast.Tuple(
                elts=[ast.Name(id=n, ctx=ast.Load()) for n in outs],
                ctx=ast.Load())))
            return ast.FunctionDef(
                name=nm,
                args=ast.arguments(posonlyargs=[], args=args, vararg=None,
                                   kwonlyargs=[], kw_defaults=[], kwarg=None,
                                   defaults=[]),
                body=stmts, decorator_list=[], returns=None)

        call = ast.Assign(
            targets=[ast.Tuple(
                elts=[ast.Name(id=n, ctx=ast.Store()) for n in outs],
                ctx=ast.Store())],
            value=ast.Call(
                func=ast.Name(id="__pdtpu_convert_ifelse", ctx=ast.Load()),
                args=[node.test,
                      ast.Name(id=tname, ctx=ast.Load()),
                      ast.Name(id=fname, ctx=ast.Load()),
                      ast.Tuple(elts=[
                          ast.Call(func=ast.Name(id="__pdtpu_maybe",
                                                 ctx=ast.Load()),
                                   args=[ast.Call(func=ast.Name(
                                       id="locals", ctx=ast.Load()),
                                       args=[], keywords=[]),
                                       ast.Constant(value=n)],
                                   keywords=[])
                          for n in outs], ctx=ast.Load())],
                keywords=[]))
        # restore python semantics for names the taken branch did not bind:
        # `if __pdtpu_is_undef(x): del x` so a later read raises
        # UnboundLocalError exactly like the untransformed code (only
        # reachable on the python-predicate path; the traced path proves
        # both branches assign)
        cleanup = [ast.If(
            test=ast.Call(func=ast.Name(id="__pdtpu_is_undef",
                                        ctx=ast.Load()),
                          args=[ast.Name(id=n, ctx=ast.Load())],
                          keywords=[]),
            body=[ast.Delete(targets=[ast.Name(id=n, ctx=ast.Del())])],
            orelse=[]) for n in outs]
        return [mk(tname, node.body), mk(fname, node.orelse), call] + cleanup

    # -- while ---------------------------------------------------------------
    def _prepare_loop_flags(self, node):
        """Rewrite break/continue in the RAW loop body into carried flags
        (ref break_continue_transformer.py).  Returns prologue statements
        binding the flags before the loop."""
        if not any(_contains_bc(s) for s in node.body):
            return []
        brk, cnt = self._fresh("brk"), self._fresh("cnt")
        node.body = (
            [ast.Assign(targets=[_name(cnt, ast.Store)],
                        value=ast.Constant(value=False))]
            + _rewrite_break_continue(node.body, brk, cnt))
        node.test = ast.Call(func=_name("__pdtpu_and_not"),
                             args=[node.test, _name(brk)], keywords=[])
        return [ast.Assign(targets=[_name(n, ast.Store)],
                           value=ast.Constant(value=False))
                for n in (brk, cnt)]

    def visit_While(self, node: ast.While):
        if node.orelse:
            raise Unsupported("while/else is outside the dy2static subset")
        prologue = self._prepare_loop_flags(node)
        self.generic_visit(node)
        _check_body(node.body)
        return prologue + self._convert_while_node(node)

    def _convert_while_node(self, node: ast.While):
        carries = sorted(set(_assigned_names(node.body)))
        if not carries:
            raise Unsupported(
                "converted `while` body assigns nothing: infinite or "
                "side-effect loop cannot become lax.while_loop")
        cname, bname = self._fresh("cond"), self._fresh("body")
        args = [ast.arg(arg=n) for n in carries]
        cond_fn = ast.FunctionDef(
            name=cname,
            args=ast.arguments(posonlyargs=[], args=args, vararg=None,
                               kwonlyargs=[], kw_defaults=[], kwarg=None,
                               defaults=[]),
            body=[ast.Return(value=node.test)], decorator_list=[],
            returns=None)
        body_stmts = list(node.body)
        body_stmts.append(ast.Return(value=ast.Tuple(
            elts=[ast.Name(id=n, ctx=ast.Load()) for n in carries],
            ctx=ast.Load())))
        body_fn = ast.FunctionDef(
            name=bname,
            args=ast.arguments(posonlyargs=[], args=args, vararg=None,
                               kwonlyargs=[], kw_defaults=[], kwarg=None,
                               defaults=[]),
            body=body_stmts, decorator_list=[], returns=None)
        call = ast.Assign(
            targets=[ast.Tuple(
                elts=[ast.Name(id=n, ctx=ast.Store()) for n in carries],
                ctx=ast.Store())],
            value=ast.Call(
                func=ast.Name(id="__pdtpu_convert_while", ctx=ast.Load()),
                args=[ast.Name(id=cname, ctx=ast.Load()),
                      ast.Name(id=bname, ctx=ast.Load()),
                      ast.Tuple(elts=[
                          ast.Call(func=ast.Name(id="__pdtpu_maybe",
                                                 ctx=ast.Load()),
                                   args=[ast.Call(func=ast.Name(
                                       id="locals", ctx=ast.Load()),
                                       args=[], keywords=[]),
                                       ast.Constant(value=n)],
                                   keywords=[])
                          for n in carries], ctx=ast.Load())],
                keywords=[]))
        return [cond_fn, body_fn, call]

    # -- for-range (ref loop_transformer.py for-range lowering) -------------
    def visit_For(self, node: ast.For):
        if node.orelse:
            raise Unsupported("for/else is outside the dy2static subset")
        it = node.iter
        if not (isinstance(it, ast.Call) and isinstance(it.func, ast.Name)
                and it.func.id == "range" and not it.keywords):
            # non-range iterables iterate in python (fine for concrete
            # sequences under trace); just convert nested constructs
            self.generic_visit(node)
            return node
        if not isinstance(node.target, ast.Name):
            raise Unsupported(
                "for-range target must be a plain name in the dy2static "
                "subset")
        i = node.target.id
        a = it.args
        if len(a) == 1:
            start, stop, step = ast.Constant(value=0), a[0], \
                ast.Constant(value=1)
        elif len(a) == 2:
            start, stop, step = a[0], a[1], ast.Constant(value=1)
        elif len(a) == 3:
            start, stop, step = a
        else:
            raise Unsupported("range() takes 1-3 arguments")
        idx_n = self._fresh("idx")
        stop_n, step_n = self._fresh("stop"), self._fresh("step")
        setup = [
            ast.Assign(targets=[_name(idx_n, ast.Store)], value=start),
            ast.Assign(targets=[_name(stop_n, ast.Store)], value=stop),
            ast.Assign(targets=[_name(step_n, ast.Store)], value=step),
            # bind the loop var before the loop so it is a lax carry (its
            # value after the loop — incl. python's "keeps the last/break
            # value" semantics — comes from the body's `i = idx` assign)
            ast.Assign(targets=[_name(i, ast.Store)], value=_name(idx_n)),
        ]
        test = ast.Call(func=_name("__pdtpu_range_cond"),
                        args=[_name(idx_n), _name(stop_n), _name(step_n)],
                        keywords=[])
        # body: i = idx; <original body>; idx = idx + step — the hidden
        # counter always advances (continue included) while `i` freezes at
        # its last assigned iteration (python for semantics, break too)
        body = [ast.Assign(targets=[_name(i, ast.Store)],
                           value=_name(idx_n))] + list(node.body)
        loop = ast.While(test=test, body=body, orelse=[])
        prologue = self._prepare_loop_flags(loop)
        loop.body.append(ast.Assign(
            targets=[_name(idx_n, ast.Store)],
            value=ast.BinOp(left=_name(idx_n), op=ast.Add(),
                            right=_name(step_n))))
        self.generic_visit(loop)
        _check_body(loop.body)
        converted = self._convert_while_node(loop)
        return setup + prologue + converted


def _maybe(frame_locals, name):
    return frame_locals.get(name, _UNDEF)


def _is_undef(x) -> bool:
    return x is _UNDEF


def ast_transform(fn: Callable) -> Callable:
    """Return fn with data-dependent if/while rewritten, or raise
    Unsupported when conversion cannot apply (caller falls back to plain
    tracing — the reference logs and falls back the same way)."""
    if inspect.ismethod(fn):
        return ast_transform(fn.__func__).__get__(fn.__self__)
    if fn.__closure__:
        raise Unsupported(
            "functions with closures are outside the dy2static subset "
            "(recompiling would sever the closure cells)")
    try:
        src = textwrap.dedent(inspect.getsource(fn))
    except (OSError, TypeError) as e:
        raise Unsupported(f"source unavailable: {e}") from e
    tree = ast.parse(src)
    fdef = tree.body[0]
    if not isinstance(fdef, (ast.FunctionDef, ast.AsyncFunctionDef)):
        raise Unsupported("not a plain function definition")
    shadowed = _shadowed_builtins(fdef)
    if not any(isinstance(n, (ast.If, ast.While, ast.For, ast.Assert))
               or (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                   and n.func.id in _REWRITABLE_BUILTINS
                   and n.func.id not in shadowed)
               for n in ast.walk(fdef)):
        raise Unsupported("nothing to convert")
    fdef.decorator_list = []  # strip @to_static etc. to avoid recursion
    new_tree = _Transformer(shadowed=shadowed).visit(tree)
    ast.fix_missing_locations(new_tree)
    code = compile(new_tree, f"<dy2static {fn.__qualname__}>", "exec")
    glb = dict(fn.__globals__)
    glb["__pdtpu_convert_ifelse"] = convert_ifelse
    glb["__pdtpu_convert_while"] = convert_while
    glb["__pdtpu_maybe"] = _maybe
    glb["__pdtpu_is_undef"] = _is_undef
    glb["__pdtpu_and_not"] = _and_not
    glb["__pdtpu_not_skipping"] = _not_skipping
    glb["__pdtpu_range_cond"] = _range_cond
    glb["__pdtpu_convert_assert"] = convert_assert
    glb["__pdtpu_convert_cast"] = convert_cast
    glb["__pdtpu_convert_print"] = convert_print
    glb["__pdtpu_convert_len"] = convert_len
    loc: dict = {}
    exec(code, glb, loc)
    out = loc[fdef.name]
    functools.update_wrapper(out, fn)
    return out

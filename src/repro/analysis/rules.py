"""The contract rules (DESIGN.md §10 maps each to the PR that set it).

Every rule is a pure function of the parsed file plus shared context, and
every finding names the violated contract so the fix (or the pragma
reason) can be reviewed against it.
"""
from __future__ import annotations

import ast
from typing import Iterable, Optional

from repro.analysis.engine import (Context, Finding, SourceFile,
                                   register_rule)

# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------


def dotted(node: ast.AST) -> Optional[str]:
    """'jax.numpy.asarray'-style dotted name of a Name/Attribute chain."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def import_aliases(tree: ast.Module) -> dict[str, str]:
    """Local name → canonical dotted module for every import in the file."""
    out: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                out[a.asname or a.name] = f"{node.module}.{a.name}"
    return out


def canonical(node: ast.AST, aliases: dict[str, str]) -> Optional[str]:
    """Dotted chain with its head import-alias expanded:
    ``jnp.asarray`` → ``jax.numpy.asarray`` under ``import jax.numpy as
    jnp``."""
    d = dotted(node)
    if d is None:
        return None
    head, _, rest = d.partition(".")
    root = aliases.get(head, head)
    return f"{root}.{rest}" if rest else root


def walk_with_function(tree: ast.Module):
    """Yield ``(node, enclosing_function_node_or_None)`` for every node."""
    def rec(node, fn):
        for child in ast.iter_child_nodes(node):
            nfn = (child if isinstance(child, (ast.FunctionDef,
                                               ast.AsyncFunctionDef,
                                               ast.Lambda)) else fn)
            yield child, fn
            yield from rec(child, nfn)
    yield from rec(tree, None)


def _in_file(rel: str, prefixes: Iterable[str]) -> bool:
    return any(rel == p or rel.startswith(p) for p in prefixes)


JIT_WRAPPERS = ("jax.jit", "jax.pmap", "jax.experimental.pjit.pjit")


def _jit_calls(sf: SourceFile, aliases):
    """(call_node, enclosing_fn, canonical_name) for jit/pmap wrappers."""
    for node, fn in walk_with_function(sf.tree):
        if isinstance(node, ast.Call):
            name = canonical(node.func, aliases)
            if name in JIT_WRAPPERS:
                yield node, fn, name


# ---------------------------------------------------------------------------
# Rule: jit-outside-cache  (contract from PR 2/PR 7's shared jit suite)
# ---------------------------------------------------------------------------

@register_rule(
    "jit-outside-cache",
    "jax.jit/jax.pmap construction belongs in the sanctioned jit-suite "
    "modules (core/client.py, serve/engine.py, sharding/) or at module "
    "scope; per-call construction elsewhere makes a fresh trace cache "
    "and defeats jit_cache_stats()'s program pins.")
def jit_outside_cache(sf: SourceFile, ctx: Context):
    if _in_file(sf.rel, ctx.config.jit_sanctioned):
        return
    aliases = import_aliases(sf.tree)
    for node, fn, name in _jit_calls(sf, aliases):
        if fn is None:
            # module scope: compiled once per import / static signature —
            # the hazard is a fresh jitted callable per call or instance
            continue
        yield Finding(
            sf.rel, node.lineno, "jit-outside-cache",
            f"{name} constructed inside {getattr(fn, 'name', '<lambda>')}() "
            f"outside the sanctioned jit-suite modules: each call builds a "
            f"fresh trace cache (recompiles every invocation; invisible to "
            f"jit_cache_stats)")


# ---------------------------------------------------------------------------
# Rule: host-sync  (contract from PR 2/PR 4's streaming pipeline)
# ---------------------------------------------------------------------------

SYNC_ATTR_CALLS = ("item", "block_until_ready")
SYNC_FUNCS = ("jax.device_get", "numpy.asarray", "numpy.array",
              "jax.block_until_ready")


@register_rule(
    "host-sync",
    "No device→host synchronisation inside functions reachable from the "
    "round/serve hot loops: .item(), float()/int() on arrays, "
    "np.asarray, jax.device_get, block_until_ready stall the async "
    "dispatch stream that the 2.8–8.6× pipeline wins depend on.")
def host_sync(sf: SourceFile, ctx: Context):
    cfg = ctx.config
    reach = ctx.callgraph.reachable(set(cfg.hot_entry_points),
                                    cfg.host_stage_boundary)
    here = [f for f in reach if f.rel == sf.rel]
    if not here:
        return
    aliases = import_aliases(sf.tree)
    for info in here:
        for node in ast.walk(info.node):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in SYNC_ATTR_CALLS:
                yield Finding(
                    sf.rel, node.lineno, "host-sync",
                    f".{f.attr}() in {info.qualname} (reachable from "
                    f"{'/'.join(cfg.hot_entry_points)}) forces a device "
                    f"sync in the hot path")
                continue
            name = canonical(f, aliases)
            if name in SYNC_FUNCS:
                yield Finding(
                    sf.rel, node.lineno, "host-sync",
                    f"{name}(...) in {info.qualname} materialises to host "
                    f"inside the hot path — move it to a round boundary "
                    f"or annotate the sanctioned sync point")
            elif (isinstance(f, ast.Name) and f.id in ("float", "int")
                  and node.args
                  and not isinstance(node.args[0], ast.Constant)):
                yield Finding(
                    sf.rel, node.lineno, "host-sync",
                    f"{f.id}(...) on a non-literal in {info.qualname} "
                    f"blocks on the device value if it is a jax array")


# ---------------------------------------------------------------------------
# Rule: nondeterminism  (contract from PR 6's flat rng streams)
# ---------------------------------------------------------------------------

SEEDED_CTORS = ("RandomState", "default_rng", "Generator", "SeedSequence",
                "PRNGKey", "key")
TIME_FUNCS = ("time.time", "time.time_ns", "time.perf_counter",
              "time.monotonic")


@register_rule(
    "nondeterminism",
    "Round/selection/state code draws entropy only from seeded, "
    "checkpointable streams (PR 6's ClientStreamState / explicit "
    "RandomState): the global random module, wall clocks, and numpy's "
    "global generator break bit-exact resume and the engine-parity "
    "oracles.")
def nondeterminism(sf: SourceFile, ctx: Context):
    if not _in_file(sf.rel, ctx.config.nondet_scope):
        return
    aliases = import_aliases(sf.tree)
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        name = canonical(node.func, aliases)
        if name is None:
            continue
        if name.startswith("random."):
            yield Finding(
                sf.rel, node.lineno, "nondeterminism",
                f"stdlib {name}(...) uses the unseeded global generator — "
                f"draw from the server/task RandomState streams instead")
        elif name in TIME_FUNCS:
            yield Finding(
                sf.rel, node.lineno, "nondeterminism",
                f"{name}(...) is wall-clock state: fine for telemetry "
                f"(annotate it), never as an input to round math")
        elif name.startswith("numpy.random."):
            tail = name.rsplit(".", 1)[1]
            if tail not in SEEDED_CTORS:
                yield Finding(
                    sf.rel, node.lineno, "nondeterminism",
                    f"{name}(...) draws from numpy's global generator — "
                    f"use an explicitly seeded RandomState/stream")
            elif not node.args and not node.keywords:
                yield Finding(
                    sf.rel, node.lineno, "nondeterminism",
                    f"{name}() without a seed is entropy from the OS — "
                    f"pass an explicit seed")


# ---------------------------------------------------------------------------
# Rule: tracer-hazard  (contract from PR 1/PR 5's jitted round programs)
# ---------------------------------------------------------------------------

TRACED_MODULES = ("jax.numpy.", "jax.lax.", "jax.nn.")
TRACED_ATTR_TESTS = ("any", "all", "item")


def _jit_registered_functions(sf: SourceFile, aliases):
    """Function defs that become jitted programs: decorated with jax.jit
    (directly or via functools.partial), or referenced by name as the
    first argument of a jax.jit(...) call anywhere in the file — the
    jit-suite registration pattern — plus every def nested inside one."""
    jitted_names: set[str] = set()
    for node, _fn, _name in _jit_calls(sf, aliases):
        if node.args:
            target = node.args[0]
            if isinstance(target, ast.Attribute):
                jitted_names.add(target.attr)
            elif isinstance(target, ast.Name):
                jitted_names.add(target.id)

    def is_jit_decorator(dec) -> bool:
        if canonical(dec, aliases) in JIT_WRAPPERS:
            return True
        if isinstance(dec, ast.Call):
            if canonical(dec.func, aliases) in JIT_WRAPPERS:
                return True
            head = canonical(dec.func, aliases)
            if head in ("functools.partial", "partial") and dec.args:
                return canonical(dec.args[0], aliases) in JIT_WRAPPERS
        return False

    out = []
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if (node.name in jitted_names
                    or any(is_jit_decorator(d) for d in node.decorator_list)):
                out.append(node)
    return out


def _has_traced_call(expr: ast.AST, aliases) -> Optional[str]:
    for n in ast.walk(expr):
        if isinstance(n, ast.Call):
            name = canonical(n.func, aliases)
            if name and any(name.startswith(m) for m in TRACED_MODULES):
                return name
            f = n.func
            if isinstance(f, ast.Attribute) and f.attr in TRACED_ATTR_TESTS:
                return f".{f.attr}()"
    return None


@register_rule(
    "tracer-hazard",
    "Inside jit-registered functions, Python `if`/`while`/`bool()` on a "
    "traced value either crashes (ConcretizationTypeError) or — worse — "
    "silently bakes one branch into the compiled program and retraces "
    "per value, breaking the one-program-per-hot-path pin.")
def tracer_hazard(sf: SourceFile, ctx: Context):
    aliases = import_aliases(sf.tree)
    for fdef in _jit_registered_functions(sf, aliases):
        for node in ast.walk(fdef):
            if isinstance(node, (ast.If, ast.While)):
                hit = _has_traced_call(node.test, aliases)
                if hit:
                    kw = "if" if isinstance(node, ast.If) else "while"
                    yield Finding(
                        sf.rel, node.lineno, "tracer-hazard",
                        f"Python `{kw}` on traced expression ({hit}) "
                        f"inside jitted {fdef.name}(): use jnp.where / "
                        f"lax.cond, or hoist to a static argument")
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Name)
                  and node.func.id == "bool"):
                yield Finding(
                    sf.rel, node.lineno, "tracer-hazard",
                    f"bool(...) inside jitted {fdef.name}() concretises "
                    f"a tracer (host round-trip or trace error)")


# ---------------------------------------------------------------------------
# Rule: unhashable-static  (contract from PR 2's (ArchConfig, RuntimeConfig)
# cache keys and the suites' static tail arguments)
# ---------------------------------------------------------------------------

MUTABLE_LITERALS = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
                    ast.SetComp)
MUTABLE_CTORS = ("list", "dict", "set", "bytearray")
# keywords whose values end up as static jit args / cache-key components
STATIC_KEYWORDS = ("reqs",)


@register_rule(
    "unhashable-static",
    "Everything used as a jit static argument or a jit-suite cache-key "
    "component must be hashable: no mutable default arguments, tuple (not "
    "list) static_argnums/static_argnames, and tuple-valued `reqs` "
    "probe-requirement sets.")
def unhashable_static(sf: SourceFile, ctx: Context):
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            args = node.args
            named = list(args.args) + list(args.posonlyargs) \
                + list(args.kwonlyargs)
            defaults = list(args.defaults) + [d for d in args.kw_defaults
                                              if d is not None]
            for d in defaults:
                bad = isinstance(d, MUTABLE_LITERALS) or (
                    isinstance(d, ast.Call)
                    and isinstance(d.func, ast.Name)
                    and d.func.id in MUTABLE_CTORS)
                if bad:
                    fname = getattr(node, "name", "<lambda>")
                    yield Finding(
                        sf.rel, d.lineno, "unhashable-static",
                        f"mutable default argument in {fname}() — shared "
                        f"across calls and unhashable as a static/"
                        f"cache-key value; use None or a tuple")
            del named
        elif isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg in ("static_argnums", "static_argnames") \
                        and isinstance(kw.value, ast.List):
                    yield Finding(
                        sf.rel, kw.value.lineno, "unhashable-static",
                        f"{kw.arg} given a list literal — the repo "
                        f"convention is a tuple (hashable, and matches "
                        f"the suite cache keys)")
                elif kw.arg in STATIC_KEYWORDS \
                        and isinstance(kw.value, MUTABLE_LITERALS):
                    yield Finding(
                        sf.rel, kw.value.lineno, "unhashable-static",
                        f"{kw.arg}= given a mutable literal — the probe "
                        f"suites take it as a static jit argument; pass "
                        f"a tuple")


# ---------------------------------------------------------------------------
# Rule: kernel-parity  (contract from PR 5/PR 7's kernel fallbacks)
# ---------------------------------------------------------------------------

@register_rule(
    "kernel-parity",
    "Every Pallas kernel module ships a pure-jnp fallback (`*_jnp`) "
    "selected off-TPU by the platform dispatch in kernels/ops.py, "
    "and a parity test in tests/test_kernels.py pins the two "
    "against each other — TPU-only code paths must never be the only "
    "implementation of round math.")
def kernel_parity(sf: SourceFile, ctx: Context):
    cfg = ctx.config
    if not sf.rel.startswith(cfg.kernel_dir):
        return
    base = sf.rel.rsplit("/", 1)[1]
    if base in cfg.kernel_exclude:
        return
    aliases = import_aliases(sf.tree)
    pallas_lines = [
        node.lineno for node in ast.walk(sf.tree)
        if isinstance(node, ast.Call)
        and (canonical(node.func, aliases) or "").endswith("pallas_call")]
    if not pallas_lines:
        return
    line = min(pallas_lines)
    stem = base[:-3]
    fallbacks = [
        n.name for n in ast.walk(sf.tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        and n.name.endswith("_jnp") and not n.name.startswith("_")]
    if not fallbacks:
        yield Finding(
            sf.rel, line, "kernel-parity",
            f"{base} calls pallas_call but defines no public *_jnp "
            f"fallback — off-TPU runs have no bit-traceable reference "
            f"for this kernel")
    dispatch_src = ctx.read_rel(cfg.kernel_dispatch)
    if dispatch_src is not None and sf.rel != cfg.kernel_dispatch \
            and stem not in dispatch_src:
        yield Finding(
            sf.rel, line, "kernel-parity",
            f"{base} is not referenced by {cfg.kernel_dispatch} — the "
            f"kernel is unreachable from the platform dispatch")
    tests_src = ctx.read_rel(cfg.kernel_tests)
    if tests_src is None or stem not in tests_src:
        yield Finding(
            sf.rel, line, "kernel-parity",
            f"{base} has no matching parity coverage in "
            f"{cfg.kernel_tests} (module name never mentioned)")
    else:
        for fb in fallbacks:
            if fb not in tests_src:
                yield Finding(
                    sf.rel, line, "kernel-parity",
                    f"fallback {fb}() is never exercised by "
                    f"{cfg.kernel_tests} — kernel/fallback parity is "
                    f"unpinned")


# ---------------------------------------------------------------------------
# Rule: donation-miss  (contract from PR 7's donated serve writes, audited
# program-side by repro.analysis.program's donation-honored contract)
# ---------------------------------------------------------------------------


def _resolve_jit_target(call: ast.Call, sf: SourceFile):
    """File-local FunctionDef/Lambda the jit call wraps, or None.

    Handles ``jax.jit(fn)``, ``jax.jit(self._impl)`` and
    ``jax.jit(lambda ...)``; targets defined in other modules resolve to
    None and are skipped (the rule only reasons about signatures it can
    see)."""
    if not call.args:
        return None
    target = call.args[0]
    if isinstance(target, ast.Lambda):
        return target
    name = None
    if isinstance(target, ast.Name):
        name = target.id
    elif isinstance(target, ast.Attribute):
        name = target.attr
    if name is None:
        return None
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == name:
            return node
    return None


@register_rule(
    "donation-miss",
    "jax.jit calls in serve/ and core/ whose wrapped function takes a "
    "params-sized tree (params/stacked/leaves/cache/bank/...) must declare "
    "donate_argnums (buffer reuse is the point of the in-place write "
    "programs) or carry a reasoned pragma naming why the buffer must "
    "survive the call — the program auditor then verifies declared "
    "donations are actually applied by XLA.")
def donation_miss(sf: SourceFile, ctx: Context):
    cfg = ctx.config
    if not _in_file(sf.rel, cfg.donation_scope):
        return
    aliases = import_aliases(sf.tree)
    tree_names = set(cfg.donation_tree_params)
    for node, _fn, name in _jit_calls(sf, aliases):
        if any(kw.arg in ("donate_argnums", "donate_argnames")
               for kw in node.keywords):
            continue
        target = _resolve_jit_target(node, sf)
        if target is None:
            continue
        args = target.args
        named = [a.arg for a in
                 list(args.posonlyargs) + list(args.args)
                 + list(args.kwonlyargs)]
        hit = [a for a in named if a in tree_names]
        if hit:
            tname = getattr(target, "name", "<lambda>")
            yield Finding(
                sf.rel, node.lineno, "donation-miss",
                f"{name}({tname}) takes params-sized tree argument(s) "
                f"{hit} but declares no donate_argnums — the caller's "
                f"buffer is copied, not reused; donate it or pragma the "
                f"reason the old buffer must stay alive")


# ---------------------------------------------------------------------------
# Rule: exception-swallow  (contract from the fault harness, DESIGN.md §12)
# ---------------------------------------------------------------------------

@register_rule(
    "exception-swallow",
    "failure-handling code in core/, ckpt/, serve/, faults/ and launch/ "
    "must not silently swallow exceptions: a bare 'except:' that never "
    "re-raises, or an 'except Exception/BaseException:' whose body is "
    "only pass/continue/..., hides exactly the faults the degradation "
    "contracts are supposed to surface (count, warn, fall back — never "
    "ignore).  Narrow the handler to the expected types, or pragma the "
    "reason swallowing is genuinely safe.")
def exception_swallow(sf: SourceFile, ctx: Context):
    if not _in_file(sf.rel, ctx.config.swallow_scope):
        return
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            if not any(isinstance(n, ast.Raise) for n in ast.walk(node)):
                yield Finding(
                    sf.rel, node.lineno, "exception-swallow",
                    "bare 'except:' with no re-raise swallows every "
                    "failure (including KeyboardInterrupt) — name the "
                    "expected exception types or re-raise")
            continue
        name = dotted(node.type)
        if name not in ("Exception", "BaseException"):
            continue                      # narrow/tuple handlers are fine
        body_is_noop = all(
            isinstance(stmt, (ast.Pass, ast.Continue))
            or (isinstance(stmt, ast.Expr)
                and isinstance(stmt.value, ast.Constant))
            for stmt in node.body)
        if body_is_noop:
            yield Finding(
                sf.rel, node.lineno, "exception-swallow",
                f"'except {name}: pass' silently discards the failure — "
                f"handle it (count/warn/fall back), narrow the type, or "
                f"pragma why ignoring it is safe")

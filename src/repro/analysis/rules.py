"""Named lint rules: the repo's written-down invariants as AST checks.

Each rule guards a contract that regressed once before it was written
down (docs/ARCHITECTURE.md "Invariants & enforcement" maps rule ->
contract -> the PR that first broke it). A rule is a pure function over
one parsed source file; the engine in ``repro.analysis.lint`` handles
file iteration, ``# repro-lint: disable=<rule> (<reason>)`` pragmas and
the baseline. Rules are *individually* suppressible and every
suppression must state a reason — a reasonless pragma is itself a
violation (``bad-pragma``).

Path scoping uses posix suffixes (e.g. ``core/substrate.py``) so the
rules behave identically whether the engine was pointed at the repo
root, ``src/``, or the package directory.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Callable, Dict, Iterator, List, Optional, Tuple

__all__ = ["Rule", "RULES", "TAU_NAMES", "ROUND_PATH_FILES"]


@dataclasses.dataclass(frozen=True)
class Rule:
    """One named invariant. ``check(ctx)`` yields (lineno, message);
    ``checker=None`` marks engine-level rules (emitted by the lint
    engine itself, e.g. ``bad-pragma``) that still need docs/pragma
    handling."""

    name: str
    description: str
    check: Optional[Callable[["FileContext"], Iterator[Tuple[int, str]]]]


@dataclasses.dataclass
class FileContext:
    """One parsed source file as the rules see it."""

    path: str            # posix path, e.g. "src/repro/core/dfl.py"
    tree: ast.Module
    lines: List[str]

    def matches(self, *suffixes: str) -> bool:
        return any(self.path.endswith(s) for s in suffixes)

    def in_dir(self, fragment: str) -> bool:
        return fragment in self.path


def _dotted(node: ast.AST) -> Optional[str]:
    """Resolve an Attribute/Name chain to 'a.b.c' (None for computed)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


# ---------------------------------------------------------------------------
# compat-boundary
# ---------------------------------------------------------------------------

# Version-sensitive JAX APIs: each spelling below changed (or appeared)
# across JAX releases. core/substrate.py is the ONE module allowed to touch
# them; everything else uses its wrappers.
_COMPAT_ATTRS = {
    "jax.lax.axis_size",
    "lax.axis_size",
    "jax.shard_map",
    "jax.make_mesh",        # default axis types changed (Auto -> Explicit)
}
_COMPAT_IMPORT_MODULES = ("jax.experimental.shard_map",)
_COMPAT_KWARGS = {"check_rep", "check_vma"}  # renamed across versions
_COMPAT_HASATTR_PROBES = {"shard_map", "axis_size", "check_vma", "check_rep"}


def _check_compat_boundary(ctx: FileContext):
    if ctx.matches("core/substrate.py"):
        return
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom) and node.module and (
                node.module.startswith(_COMPAT_IMPORT_MODULES)):
            yield node.lineno, (
                f"import from {node.module!r}: version-sensitive shard_map "
                "entry point — use repro.core.substrate.shard_map")
        elif isinstance(node, ast.Attribute):
            name = _dotted(node)
            if name in _COMPAT_ATTRS:
                yield node.lineno, (
                    f"{name}: version-sensitive JAX API — use the "
                    "repro.core.substrate wrapper")
        elif isinstance(node, ast.Call):
            fname = _dotted(node.func) or ""
            for kw in node.keywords:
                if kw.arg in _COMPAT_KWARGS:
                    yield node.lineno, (
                        f"keyword {kw.arg!r}: renamed across JAX versions "
                        "(check_rep <-> check_vma) — route through "
                        "substrate.shard_map(check=...)")
            if (fname.split(".")[-1] == "psum" and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and node.args[0].value == 1):
                yield node.lineno, (
                    "psum(1, axis): the axis-size compat shim — call "
                    "substrate.axis_size(axis) instead")
            if (fname == "hasattr" and len(node.args) == 2
                    and isinstance(node.args[1], ast.Constant)
                    and node.args[1].value in _COMPAT_HASATTR_PROBES):
                yield node.lineno, (
                    f"hasattr(..., {node.args[1].value!r}): JAX "
                    "feature-probing belongs in core/substrate.py")


# ---------------------------------------------------------------------------
# no-import-time-backend-probe
# ---------------------------------------------------------------------------

_BACKEND_PROBES = {
    "jax.devices", "jax.local_devices", "jax.device_count",
    "jax.local_device_count", "jax.default_backend", "jax.process_count",
    "jax.lib.xla_bridge.get_backend", "jax.extend.backend.get_backend",
}


def _check_import_time_probe(ctx: FileContext):
    # Module scope = executed at import. Class bodies execute at import
    # too, so they stay "module scope"; only function/lambda bodies are
    # deferred. (Decorators and default-arg expressions also run at
    # import but probing there is unheard of — not modeled.)
    def visit(node: ast.AST, in_func: bool):
        for child in ast.iter_child_nodes(node):
            child_in_func = in_func or isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda))
            if not in_func and isinstance(child, ast.Call):
                name = _dotted(child.func)
                if name in _BACKEND_PROBES:
                    yield child.lineno, (
                        f"{name}() at module scope: import-time backend "
                        "probe (the ops.ON_TPU regression class) — detect "
                        "lazily inside a function "
                        "(see kernels/registry.backend())")
            yield from visit(child, child_in_func)

    yield from visit(ctx.tree, False)


# ---------------------------------------------------------------------------
# no-host-coercion-of-device-scalars
# ---------------------------------------------------------------------------

TAU_NAMES = {"tau", "tau1", "tau2", "taus", "t1", "t2", "round_idx",
             "tau_1", "tau_2"}

# Modules on the round/superstep hot path: every int()/float()/.item()
# there runs under trace, where a host coercion is a
# ConcretizationTypeError at best and a silent recompile/sync at worst.
ROUND_PATH_FILES = ("core/dfl.py", "core/sharded.py", "core/substrate.py",
                    "core/mixing.py", "core/compression.py")
_HOST_COERCIONS = {"int", "float"}
_NP_COERCIONS = {"np.asarray", "numpy.asarray", "np.array", "numpy.array"}


def _mentions_tau(node: ast.AST) -> Optional[str]:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id in TAU_NAMES:
            return n.id
        if isinstance(n, ast.Attribute) and n.attr in TAU_NAMES:
            return n.attr
    return None


def _check_host_coercion(ctx: FileContext):
    on_round_path = ctx.matches(*ROUND_PATH_FILES)
    is_executor = ctx.matches("core/executor.py")
    if not (on_round_path or is_executor):
        return

    # executor.py's methods legitimately coerce on the host (dispatch
    # bounds checks, metric rows); only its NESTED functions (the
    # closures jit actually traces: superstep/body) are round code.
    def visit(node: ast.AST, depth: int):
        for child in ast.iter_child_nodes(node):
            d = depth + isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda))
            if isinstance(child, ast.Call) and (on_round_path or d >= 2):
                yield from check_call(child)
            yield from visit(child, d)

    def check_call(call: ast.Call):
        fname = _dotted(call.func) or ""
        target = None
        if fname in _HOST_COERCIONS and call.args:
            target = call.args[0]
        elif fname in _NP_COERCIONS and call.args:
            target = call.args[0]
        elif (isinstance(call.func, ast.Attribute)
              and call.func.attr == "item"):
            target = call.func.value
        if target is None:
            return
        tau = _mentions_tau(target)
        if tau:
            yield call.lineno, (
                f"host coercion {fname or '.item()'} of {tau!r} in round "
                "code: (tau1, tau2)/round_idx are DEVICE scalars here — a "
                "host read is a recompile or sync point (keep them traced; "
                "see core/executor.py)")

    yield from visit(ctx.tree, 0)


# ---------------------------------------------------------------------------
# rng-discipline
# ---------------------------------------------------------------------------

_RAW_KEY_CALLS = {"jax.random.PRNGKey", "jax.random.key", "random.PRNGKey",
                  "jrandom.PRNGKey", "jrandom.key", "jr.PRNGKey", "jr.key"}


def _check_rng_discipline(ctx: FileContext):
    if not ctx.matches(*ROUND_PATH_FILES):
        return
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            if name in _RAW_KEY_CALLS:
                yield node.lineno, (
                    f"{name}(...) inside a round_body-reachable module: "
                    "keys must arrive via the fold_in chain "
                    "(core.dfl.round_keys) — a raw key here silently "
                    "breaks dense<->sparse bitwise parity")


# ---------------------------------------------------------------------------
# no-disable-jit
# ---------------------------------------------------------------------------


def _check_no_disable_jit(ctx: FileContext):
    if not ctx.in_dir("repro/kernels/"):
        return
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Attribute) and (
                _dotted(node) in ("jax.disable_jit", "jax.config.disable_jit")):
            yield node.lineno, (
                "jax.disable_jit in kernels/: pallas interpret-mode kernels "
                "RECURSE under disable_jit on the pinned jaxlib "
                "(tests/test_kernels.py pins it) — use ops.eager_impl() for "
                "un-jitted instrumentation instead")


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

RULES: Dict[str, Rule] = {
    r.name: r
    for r in [
        Rule(
            "compat-boundary",
            "Version-sensitive JAX APIs (make_mesh, shard_map, axis_size, "
            "check_rep/check_vma, psum(1, axis), hasattr probes) only in "
            "core/substrate.py; everything else goes through its wrappers.",
            _check_compat_boundary,
        ),
        Rule(
            "no-import-time-backend-probe",
            "No jax.devices()/default_backend()/platform checks at module "
            "scope — backend detection must be lazy (first call).",
            _check_import_time_probe,
        ),
        Rule(
            "no-host-coercion-of-device-scalars",
            "No int()/float()/.item()/np.asarray on tau/round-idx scalars "
            "in round/superstep code paths — each is a silent recompile or "
            "host sync.",
            _check_host_coercion,
        ),
        Rule(
            "rng-discipline",
            "No raw PRNGKey construction in round_body-reachable modules; "
            "keys arrive via the round_keys fold_in chain.",
            _check_rng_discipline,
        ),
        Rule(
            "no-disable-jit",
            "jax.disable_jit is forbidden in src/repro/kernels/ (pallas "
            "interpret kernels recurse under it on the pinned jaxlib).",
            _check_no_disable_jit,
        ),
        Rule(
            "bad-pragma",
            "Every `# repro-lint: disable=<rule>` pragma must name a known "
            "rule and carry a (reason) — no silent allowlisting.",
            None,  # emitted by the engine while applying pragmas
        ),
    ]
}

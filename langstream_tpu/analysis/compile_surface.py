"""LSA3xx — compile-surface lint: the "one program per family"
invariant that keeps ``stats()["compiled_programs"]`` flat.

Every ``jax.jit`` site is a distinct XLA program family; a jit that
sneaks into a per-request path (or whose operand shapes derive from a
per-request Python value) is a 15-23s mid-traffic compile stall. The
warmed ladder is therefore a REGISTRY: the modules below declare how
many jit sites they own, and adding/removing one anywhere in the tree
is a finding until the registry (and the warmup that covers it) is
updated deliberately.

- LSA301  a ``jax.jit`` site in a module absent from the warmed-program
          registry, or a module whose site count drifted from its
          registered value (new unwarmed program family / stale
          registry)
- LSA302  a ``jax.jit`` site lexically inside a ``for``/``while`` loop
          — a program family per iteration, the exact anti-pattern the
          fixed prefill-bucket ladder exists to prevent
- LSA303  a call to a jitted entry point whose operand slice is bounded
          by ``len(...)`` — a traced shape deriving from a per-request
          Python value (one compile per distinct length)
"""

from __future__ import annotations

import ast
from typing import Optional

from langstream_tpu.analysis.core import Finding, ParsedFile, Repo

#: the warmed compile surface: module -> number of jit sites it owns.
#: Every entry is covered by a warmup path (engine precompile ladder,
#: module-import-time definition, or a build-once factory). Adding a
#: jit site ANYWHERE means updating this registry — that diff line is
#: the reviewer's cue to ask "what warms it, and what are its static
#: shapes?" (docs/ANALYSIS.md).
WARMED_MODULES: dict[str, int] = {
    "langstream_tpu/agents/vector/__init__.py": 1,   # in-memory top-k probe
    "langstream_tpu/ai/tpu_serving.py": 1,           # embedding encode
    "langstream_tpu/models/streamload.py": 2,        # build-once loaders
    "langstream_tpu/models/transformer.py": 3,       # forward/prefill/decode_step
    "langstream_tpu/parallel/sp.py": 1,              # long-context ring
    "langstream_tpu/serving/adapters.py": 1,         # LoRA row swap
    "langstream_tpu/serving/constrain.py": 1,        # grammar mask load
    "langstream_tpu/serving/engine.py": 12,          # the warmed paged programs (+ a window model's `_window_page_zero`, warmed with `_page_zero`; + a block-filling model's `_block_admit_group` and `_paged_block_chunk`, warmed where the admit group and the decode chunk are: `_dev_paged_prefill`, `_dev_decode`)
    "langstream_tpu/serving/sampling.py": 2,         # sample/verify kernels
}


def _is_jit_ref(node: ast.AST) -> bool:
    """An occurrence of the ``jax.jit`` callable itself: ``jax.jit``
    attribute access, or a bare ``jit`` name imported from jax."""
    if isinstance(node, ast.Attribute) and node.attr == "jit":
        v = node.value
        return isinstance(v, ast.Name) and v.id == "jax"
    return False


def _jit_sites(pf: ParsedFile) -> list[ast.AST]:
    sites = []
    jit_names = {"jit"} if _imports_jit_name(pf) else set()
    for node in ast.walk(pf.tree):
        if _is_jit_ref(node):
            sites.append(node)
        elif isinstance(node, ast.Name) and node.id in jit_names:
            # only count LOAD uses (a decorator/call), not stores
            if isinstance(node.ctx, ast.Load):
                sites.append(node)
    return sites


def _imports_jit_name(pf: ParsedFile) -> bool:
    for node in ast.walk(pf.tree):
        if isinstance(node, ast.ImportFrom) and node.module == "jax":
            if any(a.name == "jit" for a in node.names):
                return True
    return False


def _in_loop(node: ast.AST) -> Optional[ast.AST]:
    from langstream_tpu.analysis.core import parents

    prev: ast.AST = node
    for p in parents(node):
        if isinstance(p, (ast.For, ast.AsyncFor, ast.While)):
            return p
        if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a jit applied as THIS function's decorator still belongs
            # to the enclosing scope (a loop around the def re-jits per
            # iteration); a jit in the function BODY is warmed when the
            # factory runs once at build time
            if prev not in p.decorator_list:
                return None
        prev = p
    return None


def _jitted_local_names(pf: ParsedFile) -> set[str]:
    """Names bound to jitted callables in this module: decorated defs
    and ``name = jax.jit(...)`` / ``name = functools.partial(jax.jit,…)``
    assignments."""
    names: set[str] = set()
    for node in ast.walk(pf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                if _is_jit_ref(target) or (
                    isinstance(dec, ast.Call)
                    and any(_is_jit_ref(a) for a in dec.args)
                ):
                    names.add(node.name)
        elif isinstance(node, ast.Assign) and isinstance(
            node.value, ast.Call
        ):
            call = node.value
            if _is_jit_ref(call.func) or any(
                _is_jit_ref(a) for a in call.args
            ):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
    return names


def _len_bounded_slice(node: ast.AST) -> Optional[ast.AST]:
    """A subscript argument sliced to ``len(...)`` anywhere inside the
    expression: the per-request-shape heuristic."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Subscript) and isinstance(
            sub.slice, ast.Slice
        ):
            for bound in (sub.slice.lower, sub.slice.upper):
                if (
                    isinstance(bound, ast.Call)
                    and isinstance(bound.func, ast.Name)
                    and bound.func.id == "len"
                ):
                    return sub
    return None


def check(repo: Repo) -> list[Finding]:
    findings: list[Finding] = []
    seen_modules: set[str] = set()
    for pf in repo.files:
        if pf.rel.startswith("langstream_tpu/analysis/"):
            continue
        sites = _jit_sites(pf)
        if sites:
            seen_modules.add(pf.rel)
        expected = WARMED_MODULES.get(pf.rel)
        if sites and expected is None:
            for site in sites:
                findings.append(
                    Finding(
                        code="LSA301",
                        path=pf.rel,
                        line=site.lineno,
                        message=(
                            "jax.jit site in a module outside the "
                            "warmed-program registry "
                            "(analysis/compile_surface.WARMED_MODULES) — "
                            "register it and say what warms it"
                        ),
                    )
                )
        elif expected is not None and len(sites) != expected:
            line = sites[0].lineno if sites else 1
            findings.append(
                Finding(
                    code="LSA301",
                    path=pf.rel,
                    line=line,
                    message=(
                        f"module owns {len(sites)} jax.jit site(s) but "
                        f"the warmed-program registry says {expected} — "
                        "update analysis/compile_surface.WARMED_MODULES "
                        "with the warmup story for the change"
                    ),
                )
            )
        for site in sites:
            loop = _in_loop(site)
            if loop is not None:
                findings.append(
                    Finding(
                        code="LSA302",
                        path=pf.rel,
                        line=site.lineno,
                        message=(
                            "jax.jit inside a loop compiles one program "
                            "family per iteration — hoist it to module "
                            "scope or a build-once factory"
                        ),
                    )
                )
        jitted = _jitted_local_names(pf)
        if jitted:
            for node in ast.walk(pf.tree):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in jitted
                ):
                    for arg in node.args:
                        bad = _len_bounded_slice(arg)
                        if bad is not None:
                            findings.append(
                                Finding(
                                    code="LSA303",
                                    path=pf.rel,
                                    line=node.lineno,
                                    message=(
                                        f"operand of jitted "
                                        f"{node.func.id!r} is sliced to "
                                        "len(...) — a traced shape from "
                                        "a per-request value compiles "
                                        "one program per distinct "
                                        "length; pad to a bucket "
                                        "instead"
                                    ),
                                )
                            )
    # stale registry rows: module registered but no longer owns a site
    for rel, expected in WARMED_MODULES.items():
        if rel not in seen_modules and repo.get(rel) is not None:
            findings.append(
                Finding(
                    code="LSA301",
                    path=rel,
                    line=1,
                    message=(
                        f"warmed-program registry expects {expected} "
                        "jax.jit site(s) here but the module owns none — "
                        "drop the stale registry row"
                    ),
                )
            )
    return findings

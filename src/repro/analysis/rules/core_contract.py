"""core-contract: generated cores draw through the fused serving launch.

A generated core package (``results/generated_cores/<name>/``) is the
hand-off artifact between codegen and the serving stack: the farm
imports it and trusts that its ``generate_bits`` is bit-compatible with
gang serving.  That holds only if the core draws through the fused
``ops.chaotic_bits`` launch AND plumbs the resumability contract —
``word_offset`` in, ``(words, final_state)`` out — because the serving
tier resumes every tenant stream chunk-by-chunk from exactly those two
values.  A hand-edited or stale core that drops ``word_offset`` (or
draws via a raw trajectory + host-side fold) would serve words that
silently diverge from the solo path after the first flush boundary.

Checked per ``__init__.py``: a ``generate_bits`` function exists, takes
a ``word_offset`` parameter, and returns the ``ops.chaotic_bits(...)``
call directly with ``word_offset`` forwarded into it.

The rule guards the serving layer's side of the same contract too:
``src/repro/serve/`` must not wrap its own ``shard_map``.  Device
sharding is owned by the launch stack — ``ops.chaotic_bits_gang(...,
mesh=)`` / ``ops.chaotic_bits(..., mesh=)`` and the sharded kernels —
which carry the proven bit-identity and scalar-prefetch-slicing
contracts (tests/test_sharded_gang.py, tests/test_mesh_solo_launch.py).  A serve-layer ``shard_map`` would bypass
the gang scheduler entirely: words from such a launch are outside every
equivalence suite, the planner cannot cost it, and the compat key /
plan caches would not know its topology.
"""
from __future__ import annotations

import ast
from typing import Iterable, Optional

from repro.analysis.engine import FileContext, Finding, Rule


def _params(fn: ast.FunctionDef):
    a = fn.args
    return [p.arg for p in (a.posonlyargs + a.args + a.kwonlyargs)]


class CoreContractRule(Rule):
    name = "core-contract"
    doc = ("every generated core exposes generate_bits(word_offset=...) "
           "returning the fused ops.chaotic_bits launch; serve/ never "
           "wraps its own shard_map around one")

    def applies(self, rel: str) -> bool:
        return ((rel.startswith("results/generated_cores/")
                 and rel.endswith("__init__.py"))
                or rel.startswith("src/repro/serve/"))

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.rel.startswith("src/repro/serve/"):
            yield from self._check_serve(ctx)
            return
        fn: Optional[ast.FunctionDef] = None
        for node in ctx.tree.body:
            if isinstance(node, ast.FunctionDef) and node.name == "generate_bits":
                fn = node
                break
        if fn is None:
            yield self.finding(
                ctx, 1,
                "no generate_bits() at module level: the serving farm "
                "cannot draw from this core (regenerate it with "
                "repro.core.codegen)")
            return
        if "word_offset" not in _params(fn):
            yield self.finding(
                ctx, fn,
                "generate_bits() lacks a word_offset parameter: chunked "
                "serving cannot resume the word sequence, tenant streams "
                "diverge from the solo path at the first flush boundary")
            return
        for ret in ast.walk(fn):
            if not isinstance(ret, ast.Return) or ret.value is None:
                continue
            v = ret.value
            if (isinstance(v, ast.Call)
                    and isinstance(v.func, ast.Attribute)
                    and v.func.attr.startswith("chaotic_bits")
                    and self._forwards_word_offset(v)):
                return
        yield self.finding(
            ctx, fn,
            "generate_bits() does not return a fused ops.chaotic_bits(...) "
            "call forwarding word_offset: the core is not bit-compatible "
            "with gang serving (host-side folds or a dropped offset "
            "change the emitted words)")

    def _forwards_word_offset(self, call: ast.Call) -> bool:
        for n in ast.walk(call):
            if isinstance(n, ast.Name) and n.id == "word_offset":
                return True
        return False

    _SERVE_MSG = (
        "serve/ must not wrap its own shard_map: sharded launches route "
        "through the launch stack (ops.chaotic_bits_gang(..., mesh=) / "
        "ops.chaotic_bits(..., mesh=)), whose bit-identity to the 1-device "
        "and solo paths is proven — a direct shard_map bypasses the gang "
        "scheduler, the cost model, and the topology-keyed plan caches")

    def _check_serve(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom):
                if ((node.module and "shard_map" in node.module)
                        or any(a.name == "shard_map" for a in node.names)):
                    yield self.finding(ctx, node, self._SERVE_MSG)
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if "shard_map" in a.name:
                        yield self.finding(ctx, node, self._SERVE_MSG)
            elif isinstance(node, ast.Call):
                f = node.func
                name = (f.id if isinstance(f, ast.Name)
                        else f.attr if isinstance(f, ast.Attribute) else "")
                if name == "shard_map":
                    yield self.finding(ctx, node, self._SERVE_MSG)

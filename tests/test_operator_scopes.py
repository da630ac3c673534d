"""Operator scopes: every operator call of the fixed-point drivers runs in a
``jax.named_scope`` named for its class, so the compiled program's
``op_name`` metadata (and a profiler trace's name stack) says which
operator each op belongs to.  The scopes are metadata only: with the
metadata stripped, the optimized program is the same without them."""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import operators
from repro.core.engine import (Dataset, RecursiveQuery, build_plan,
                               query_context)
from repro.core.operators import DirectionSwitch, EngineCaps
from repro.data.treegen import TreeSpec, make_edge_table

CAPS = EngineCaps(frontier=1024, result=1024)
LANES = 4


@pytest.fixture(scope="module")
def ds():
    spec = TreeSpec(num_vertices=600, height=6, payload_cols=2, seed=3)
    d = Dataset.prepare(make_edge_table(spec), spec.num_vertices)
    d.ensure_reverse()
    return d


def _compiled_text(engine: str, ds) -> tuple[str, operators.Pipeline]:
    q = RecursiveQuery(engine, 6, 2, CAPS)
    roots = jnp.arange(LANES, dtype=jnp.int32)
    nv = ds.num_vertices
    if engine == "multiquery":
        q = dataclasses.replace(q, lanes=LANES)
        plan, ctx = build_plan(q), query_context(q, ds)
        limits = jnp.full((LANES,), 6, jnp.int32)
        # a fresh function each time: nothing is reused from a cache
        f = jax.jit(lambda c, r, lim: operators.multiquery_fixed_point(
            plan, c, r, nv, lim))
        return f.lower(ctx, roots, limits).compile().as_text(), plan
    plan, ctx = build_plan(q), query_context(q, ds)
    f = jax.jit(lambda c, r: operators.fixed_point_batch(plan, c, r, nv))
    return f.lower(ctx, roots).compile().as_text(), plan


def _without_metadata(hlo: str) -> str:
    """The module from its first computation on (the stack-frame table
    before it is debug information), every ``metadata={...}`` removed."""
    lines = hlo.splitlines()
    first = next(i for i, ln in enumerate(lines)
                 if ln.startswith(("%", "ENTRY")))
    return re.sub(r",? metadata=\{[^}]*\}", "",
                  "\n".join(lines[:1] + lines[first:]))


def _operator_classes(plan) -> set:
    ops = [plan.seed, *plan.ops, plan.finisher]
    ops += [child for op in plan.ops if isinstance(op, DirectionSwitch)
            for child in (op.push, op.pull)]
    return {type(op).__name__ for op in ops}


@pytest.mark.parametrize("engine", ["diropt", "hybrid", "multiquery"])
def test_scopes_name_every_operator_and_change_no_op(engine, ds,
                                                     monkeypatch):
    scoped, plan = _compiled_text(engine, ds)
    op_names = re.findall(r'op_name="([^"]*)"', scoped)
    named = {c for n in op_names for c in re.findall(r"[/(]([A-Z]\w*)", n)}
    assert _operator_classes(plan) <= named
    monkeypatch.setattr(operators, "_scope",
                        lambda op: contextlib.nullcontext())
    bare, _ = _compiled_text(engine, ds)
    assert not any(re.search(r"[/(][A-Z]", n)
                   for n in re.findall(r'op_name="([^"]*)"', bare))
    assert _without_metadata(scoped) == _without_metadata(bare)


def test_compile_cache_keys_on_the_scopes():
    # a cache entry written by a build without (or with other) scopes must
    # not serve this one: its profile would name the wrong operators
    from repro.launch import compile_cache

    flag = "jax_compilation_cache_include_metadata_in_key"
    saved = {k: getattr(jax.config, k)
             for k in (flag, "jax_compilation_cache_dir")}
    try:
        compile_cache.enable()
        assert getattr(jax.config, flag) is True
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)

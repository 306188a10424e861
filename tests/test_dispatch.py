"""One dispatch decision for the whole library.

Whether a Pallas kernel runs is ``ops.use_pallas()``; whether it runs in
the interpreter is ``ops._interpret()``.  These tests hold every layer to
that one switch:

* under ``REPRO_PALLAS_INTERPRET=1`` (the ``pallas_interpret`` fixture) a
  library op, prefill attention and decode attention each stage a
  ``pallas_call``; without it none does;
* ``src/repro`` reads no ``REPRO_*`` variable outside a short list, so no
  other switch can pick a kernel;
* no Pallas kernel module reaches up into a planner or a model.
"""

from __future__ import annotations

import ast
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.models import attention as attn

SRC = pathlib.Path(__file__).resolve().parent.parent / "src" / "repro"

#: the REPRO_* variables src/repro may read: the interpret switch, the
#: tuner's, the serving XLA preset's and the dry run's
ALLOWED_ENV = {
    "REPRO_PALLAS_INTERPRET",
    "REPRO_TUNE",
    "REPRO_TUNE_CACHE",
    "REPRO_SERVE_FLAGS",
    "REPRO_BF16_DOT",
    "REPRO_UNROLL_SCANS",
    "REPRO_SP",
    "REPRO_DRYRUN_DIR",
    "REPRO_DRYRUN_ACCUM",
}

#: what a kernel module may not import: the planners above the dispatch
#: layer and the models above those
PLANNERS_AND_MODELS = (
    "repro.core.plan",
    "repro.core.index_plan",
    "repro.core.stencil",
    "repro.core.dist_plan",
    "repro.models",
)

KERNEL_MODULES = sorted(
    p.name for p in (SRC / "kernels").glob("*.py") if p.name != "ops.py"
)


def _rand(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


@pytest.fixture(params=[False, True], ids=["default", "pallas_interpret"])
def interpret_switch(request):
    if request.param:
        request.getfixturevalue("pallas_interpret")
    return request.param


def test_one_switch_steers_ops_and_attention(interpret_switch):
    assert ops.use_pallas() is interpret_switch
    x = _rand((2, 8, 4, 128))
    q, k, v = _rand((1, 4, 64, 32), 1), _rand((1, 2, 64, 32), 2), _rand((1, 2, 64, 32), 3)
    q1 = _rand((1, 4, 1, 32), 4)
    calls = {
        "ops.permute": (lambda a: ops.permute(a, (0, 2, 1, 3)), (x,)),
        "flash_attention": (
            lambda a, b, c: attn.flash_attention(a, b, c, causal=True, chunk=32),
            (q, k, v),
        ),
        "decode_attention": (
            lambda a, b, c: attn.decode_attention(a, b, c, length=jnp.int32(40)),
            (q1, k, v),
        ),
    }
    for name, (fn, args) in calls.items():
        staged = bool(re.search(r"\bpallas_call\b", str(jax.make_jaxpr(fn)(*args))))
        assert staged is ops.use_pallas(), name


def test_src_reads_no_other_repro_variable():
    found = {}
    for path in SRC.rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if (
                isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and re.fullmatch(r"REPRO_[A-Z0-9_]+", node.value)
            ):
                found.setdefault(node.value, []).append(str(path.relative_to(SRC)))
    extra = {name: where for name, where in found.items() if name not in ALLOWED_ENV}
    assert not extra, extra


def _imports(path: pathlib.Path):
    """Every module an import statement of ``path`` names, lazy imports
    inside functions included (``from a import b`` counts as ``a.b``)."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            yield from (f"{node.module}.{alias.name}" for alias in node.names)


@pytest.mark.parametrize("module", KERNEL_MODULES)
def test_kernel_module_imports_no_planner_or_model(module):
    bad = sorted(
        name
        for name in set(_imports(SRC / "kernels" / module))
        if any(name == m or name.startswith(m + ".") for m in PLANNERS_AND_MODELS)
    )
    assert not bad, (module, bad)

"""Every XLA contraction of the TLR path names its matmul precision.

On a TPU an f32 contraction that names none runs as one bf16 pass, which
breaks the ARA error estimates; on the CPU the two agree, so no numerical
test here can see a contraction that forgot. This test reads the source
instead: in the modules of ``core/``, ``kernels/`` and ``serve/`` that
import JAX, every ``@`` and every multi-operand einsum/dot call must go
through ``repro.precision`` or pass ``precision=`` itself.
"""

import ast
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import precision

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"
CONTRACTIONS = {"einsum", "matmul", "dot", "vdot", "inner", "tensordot",
                "dot_general"}


def _imports_jax(tree: ast.Module) -> bool:
    for node in tree.body:
        if isinstance(node, ast.Import):
            if any(a.name.split(".")[0] == "jax" for a in node.names):
                return True
        elif isinstance(node, ast.ImportFrom) and node.module:
            if node.module.split(".")[0] == "jax":
                return True
    return False


def _unnamed_contractions(path: Path) -> list[str]:
    tree = ast.parse(path.read_text())
    if not _imports_jax(tree):
        return []   # numpy host code (dense references, point generators)
    bad = []
    for node in ast.walk(tree):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.MatMult):
            bad.append(f"{path.name}:{node.lineno} @")
        elif (isinstance(node, ast.Call)
              and isinstance(node.func, ast.Attribute)
              and node.func.attr in CONTRACTIONS
              and not (node.func.attr == "einsum" and len(node.args) < 3)
              and not any(k.arg == "precision" for k in node.keywords)):
            bad.append(f"{path.name}:{node.lineno} {ast.unparse(node.func)}")
    return bad


@pytest.mark.parametrize("package", ["core", "kernels", "serve"])
def test_contractions_name_precision(package):
    files = sorted((SRC / package).glob("*.py"))
    assert files
    bad = [b for f in files for b in _unnamed_contractions(f)]
    assert not bad, ("contractions without a named precision (use "
                     f"repro.precision): {bad}")


def test_helpers_name_highest():
    """The helpers put HIGHEST on the traced dot_general, whatever the
    process-wide default."""
    a = jnp.ones((4, 4), jnp.float32)
    with jax.default_matmul_precision("bfloat16"):
        for jaxpr in (jax.make_jaxpr(precision.matmul)(a, a),
                      jax.make_jaxpr(lambda x: precision.einsum(
                          "ij,jk->ik", x, x))(a),
                      jax.make_jaxpr(precision.vdot)(a[0], a[0])):
            dots = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
            assert dots
            for e in dots:
                assert e.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
    np.testing.assert_allclose(np.asarray(precision.matmul(a, a)), 4.0)

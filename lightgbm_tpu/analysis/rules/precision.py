"""precision-contract: the f32-Kahan / host-f64 split must not blur.

Provenance: the histogram engine's serial==parallel bit-parity
contract (Mitchell & Frank-style deterministic building,
arXiv:1806.11248) rests on chunked *f32* Kahan-pair arithmetic on
device (ops/histogram.py), and the prediction/serving reference path
reduces leaf values in host f64 (models/gbdt.py, serving). Three ways
code has tried to blur that line:

- ``jnp.float64`` in device-traced builder code: jax runs with x64
  disabled — the cast silently produces f32 on device but f64 under
  ``JAX_ENABLE_X64`` debugging, i.e. a parity break that only shows in
  the one place you can't reproduce it;
- f32 accumulation inside a host reduction whose docstring *documents*
  f64 (``np.sum(..., dtype=np.float32)`` in a "reduces in f64"
  function);
- raw ``float(...)`` on a Kahan pair value: collapsing (value,
  residual) by truncation instead of through the documented fold
  helpers (``hist_pair_fold_collapse``, ``kahan_fold``) drops the
  compensation term.

Scope: ``lightgbm_tpu/{ops,models,parallel,data}/``.
"""

import ast
import re

from ..core import Fixture, Rule, Severity, call_name, node_source, register

SCOPE_RE = re.compile(r"^lightgbm_tpu/(ops|models|parallel|data)/")
_F64_DOC = re.compile(r"\bf64\b|float64", re.I)
_PAIRISH = re.compile(r"pair|kahan", re.I)
_HOST_REDUCERS = frozenset({"sum", "cumsum", "dot", "einsum", "add.reduce"})


@register
class PrecisionContractRule(Rule):
    name = "precision-contract"
    doc = ("f64 in device-traced builders, f32 accumulation in "
           "documented-f64 host reductions, or raw float() on Kahan "
           "pairs")
    severity = Severity.ERROR

    def check(self, project):
        out = []
        for pf in project.files:
            if not SCOPE_RE.match(pf.rel):
                continue
            out.extend(self._check_file(pf))
        return out

    def _check_file(self, pf):
        out = []
        for node in ast.walk(pf.tree):
            # (1) jnp.float64 anywhere in traced-builder scope
            if isinstance(node, ast.Attribute) and node.attr == "float64":
                base = node_source(pf, node.value)
                if base in ("jnp", "jax.numpy"):
                    out.append(self.violation(
                        pf, node,
                        "jnp.float64 in device-traced builder scope — "
                        "device arithmetic is f32 by contract (x64 is "
                        "disabled; under JAX_ENABLE_X64 this silently "
                        "changes the traced program and breaks "
                        "serial==parallel bit-parity)"))
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            # (3) raw float() on a Kahan pair expression
            if name == "float" and len(node.args) == 1:
                src = node_source(pf, node.args[0])
                if _PAIRISH.search(src):
                    out.append(self.violation(
                        pf, node,
                        f"raw float() on a Kahan pair expression "
                        f"({src[:40]!r}) — collapse through the fold "
                        f"helpers (hist_pair_fold_collapse / "
                        f"kahan_fold) or the compensation term is "
                        f"silently dropped"))
        # (2) f32 accumulation in documented-f64 host reductions
        for func in pf.functions():
            doc = ast.get_docstring(func) or ""
            if not _F64_DOC.search(doc):
                continue
            for node in ast.walk(func):
                if getattr(node, "_g_func", None) is not func:
                    continue
                if not isinstance(node, ast.Call):
                    continue
                name = call_name(node)
                last = name.rsplit(".", 1)[-1]
                if last not in _HOST_REDUCERS or \
                        not name.startswith(("np.", "numpy.")):
                    continue
                for kw in node.keywords:
                    if kw.arg == "dtype" and \
                            "float32" in node_source(pf, kw.value):
                        out.append(self.violation(
                            pf, node,
                            f"{name}(dtype=float32) inside a function "
                            f"whose docstring documents f64 "
                            f"accumulation — the reduction no longer "
                            f"matches its contract"))
        return out

    def fixtures(self):
        bad = {
            "lightgbm_tpu/ops/newkern.py": (
                "import jax.numpy as jnp\n"
                "import numpy as np\n"
                "def fold(x):\n"
                "    return x.astype(jnp.float64)\n"
                "def collapse(hist_pair):\n"
                "    return float(hist_pair[0])\n"
                "def reduce_host(x):\n"
                "    \"\"\"Reduces leaf values in f64.\"\"\"\n"
                "    return np.sum(x, dtype=np.float32)\n"
            ),
        }
        good = {
            "lightgbm_tpu/ops/newkern.py": (
                "import jax.numpy as jnp\n"
                "import numpy as np\n"
                "def fold(x):\n"
                "    return x.astype(jnp.float32)\n"
                "def collapse(hist_pair):\n"
                "    hi, lo = hist_pair\n"
                "    return hi + lo\n"
                "def reduce_host(x):\n"
                "    \"\"\"Reduces leaf values in f64.\"\"\"\n"
                "    return np.sum(x, dtype=np.float64)\n"
            ),
        }
        good_host_f64 = {
            # np.float64 on HOST (outside jnp) is the contract, not a
            # violation
            "lightgbm_tpu/models/hostpath.py": (
                "import numpy as np\n"
                "def gather(leaves):\n"
                "    return np.asarray(leaves, dtype=np.float64)\n"
            ),
        }
        # the linear-leaf solver (models/linear_leaves.py) accumulates
        # per-leaf normal equations in host f64 over the canonical fit
        # chunk grid — ITS serial==out-of-core bit-parity contract.
        # Pin that an f32 downgrade of a documented-f64 accumulation in
        # leaf-solver-shaped code is caught.
        leaf_solver_bad = {
            "lightgbm_tpu/models/linsolve.py": (
                "import numpy as np\n"
                "def accumulate_normal_eq(xw, g):\n"
                "    \"\"\"Accumulates the per-leaf normal equations in\n"
                "    host f64 over canonical fit chunks (the\n"
                "    linear_leaves.py serial==streamed contract).\"\"\"\n"
                "    return np.einsum('ni,nj->ij', xw, xw,\n"
                "                     dtype=np.float32)\n"
            ),
        }
        leaf_solver_good = {
            "lightgbm_tpu/models/linsolve.py": (
                "import numpy as np\n"
                "def accumulate_normal_eq(xw, g):\n"
                "    \"\"\"Accumulates the per-leaf normal equations in\n"
                "    host f64 over canonical fit chunks (the\n"
                "    linear_leaves.py serial==streamed contract).\"\"\"\n"
                "    return np.einsum('ni,nj->ij', xw, xw,\n"
                "                     dtype=np.float64)\n"
            ),
        }
        return [
            Fixture("f64-trace-f32-doc-float-pair", bad, expect=3),
            Fixture("contract-respected", good, expect=0),
            Fixture("host-f64-legit", good_host_f64, expect=0),
            Fixture("leaf-solver-f32-downgrade", leaf_solver_bad,
                    expect=1),
            Fixture("leaf-solver-f64-contract", leaf_solver_good,
                    expect=0),
        ]

"""The seam of ``dlbb_tpu/serve/``: scheduler (``engine.py``) -> a block
family's programs (``gpt.py``, ``hybrid.py``) -> cache and attention
helpers (``kvcache.py``, ``attend.py``), ``config.py`` beside them, and
imports one way only.  ``docs/serving.md``, "Adding a block family", is
the prose of what is pinned here.
"""

from __future__ import annotations

import ast
import importlib
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
SERVE = REPO / "dlbb_tpu" / "serve"

# what the scheduler asks of every family
SEAM_FUNCTIONS = (
    "check_serving", "register_metrics", "slot_recycled", "fresh_carry",
    "create_prefix", "prompt_input", "build_prefill_chunk",
    "decode_programs", "inject_token", "attend_tiles", "report_shares",
)
SEAM_CONSTANTS = {"TOKENS_FED_BACK": bool, "PROBES": int, "LACKS": dict}
# the scheduler's names for what a family may lack; what stands behind
# each where the family has it
CAPABILITIES = {
    # a probing family's decode programs return ``(tokens, seen,
    # counts)``: it says how a chunk's ``last`` lays out as ``seen``, and
    # books the counts of a unit (what it is asked at its dispatch, what
    # it counted once it is done) and of a chunk
    "probe": ("slot_state", "probe_parts", "probe_names", "unit_dispatched",
              "unit_counted", "chunk_counted"),
    "monolithic_prefill": ("build_prefill", "build_prefix_attach"),
}


def _imports(path: Path) -> set[str]:
    """Every module a file imports, at any depth of nesting: ``import
    a.b``, ``from a.b import c`` (as ``a.b`` and ``a.b.c``)."""
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            found.add(node.module)
            found.update(f"{node.module}.{alias.name}"
                         for alias in node.names)
    return found


@pytest.mark.parametrize("family", ["gpt", "hybrid"])
def test_a_family_answers_everything_the_scheduler_asks(family):
    module = importlib.import_module(f"dlbb_tpu.serve.{family}")
    for name in SEAM_FUNCTIONS:
        assert callable(getattr(module, name, None)), name
    for name, kind in SEAM_CONSTANTS.items():
        assert isinstance(getattr(module, name, None), kind), name
    # a capability is either there or refused with a reason
    assert set(module.LACKS) <= set(CAPABILITIES)
    for capability, names in CAPABILITIES.items():
        for name in names:
            assert (capability in module.LACKS) != hasattr(module, name), \
                (capability, name)
    assert (module.PROBES > 0) == ("probe" not in module.LACKS)
    assert all(isinstance(r, str) and r for r in module.LACKS.values())


@pytest.mark.parametrize("module", ["config", "attend", "gpt", "hybrid",
                                    "kvcache"])
def test_nothing_under_the_scheduler_imports_it_or_the_fault_sites(module):
    imported = _imports(SERVE / f"{module}.py")
    assert not {m for m in imported
                if m.startswith(("dlbb_tpu.serve.engine",
                                 "dlbb_tpu.resilience.inject"))}
    # the helpers lie under both families, the envelope beside them
    if module in ("config", "attend", "kvcache"):
        assert not {m for m in imported
                    if m.startswith(("dlbb_tpu.serve.gpt",
                                     "dlbb_tpu.serve.hybrid"))}


def test_the_scheduler_names_a_family_in_one_function_only():
    tree = ast.parse((SERVE / "engine.py").read_text())
    (family_for,) = [n for n in tree.body
                     if isinstance(n, ast.FunctionDef)
                     and n.name == "family_for"]
    inside = {id(n) for n in ast.walk(family_for)}
    named = []
    for node in ast.walk(tree):
        if id(node) in inside:
            continue
        if isinstance(node, ast.Attribute) and node.attr == "is_hybrid":
            named.append((node.lineno, "is_hybrid"))
        elif isinstance(node, ast.Name) and "hybrid" in node.id.lower():
            named.append((node.lineno, node.id))
        elif isinstance(node, ast.Attribute) and "hybrid" in node.attr.lower():
            named.append((node.lineno, node.attr))
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            modules = [getattr(node, "module", None) or ""]
            modules += [alias.name for alias in node.names]
            named += [(node.lineno, m) for m in modules
                      if m.split(".")[-1] in ("hybrid", "gpt")]
    assert not named, named
    # and that function is where the engine gets its programs from
    imported = {m for n in ast.walk(family_for)
                if isinstance(n, ast.ImportFrom)
                for m in [a.name for a in n.names]}
    assert imported == {"gpt", "hybrid"}


def test_the_benchmarks_import_holds_and_a_family_loads_without_the_engine():
    code = (
        "import sys\n"
        "import dlbb_tpu.serve.hybrid\n"
        "import dlbb_tpu.serve.gpt\n"
        "assert 'dlbb_tpu.serve.engine' not in sys.modules, 'engine'\n"
        "assert 'dlbb_tpu.resilience.inject' not in sys.modules, 'inject'\n"
        # benchmarks/harness/serving.py:43, letter for letter
        "from dlbb_tpu.serve.engine import ServingConfig, ServingEngine\n"
        "from dlbb_tpu.serve import config\n"
        "assert ServingConfig is config.ServingConfig\n"
        "assert ServingEngine.__module__ == 'dlbb_tpu.serve.engine'\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]

"""System-level tests: dry-run cells in a subprocess (512 placeholder
devices), serving driver, and example smoke runs."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
ENV = dict(os.environ, PYTHONPATH=f"{ROOT}/src")


def _run(cmd, timeout=420):
    return subprocess.run(cmd, cwd=ROOT, env=ENV, capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.parametrize("arch,shape", [
    ("whisper-base", "decode_32k"),
    ("recurrentgemma-2b", "long_500k"),
])
def test_dryrun_cell_subprocess(arch, shape):
    """One real 256-chip lower+compile per family class (the full 66-cell
    matrix is artifacts/dryrun_matrix.json; this keeps CI honest)."""
    r = _run([sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
              "--shape", shape, "--mesh", "single"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert '"status": "OK"' in r.stdout


def test_dryrun_multipod_subprocess():
    r = _run([sys.executable, "-m", "repro.launch.dryrun", "--arch",
              "whisper-base", "--shape", "decode_32k", "--mesh", "multi"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "2x16x16" in r.stdout


def test_dryrun_matrix_artifact_complete():
    """The committed artifact must cover every (arch x shape x mesh) cell
    with status OK — 33 applicable cells x 2 meshes, plus the paged-kernel
    decode dispatch axis (every attention-bearing decode cell again through
    the fused pool), the speculative verify-chunk axis (the same cells at
    S = spec_k + 1) and the shard_map lane-merge axis (the paged cells with
    shard_map_pool=True) — 60 x 2 = 120."""
    path = ROOT / "artifacts" / "dryrun_matrix.json"
    if not path.exists():
        pytest.skip("matrix artifact not built yet (scripts/run_matrices.sh)")
    rows = json.loads(path.read_text())
    from repro import configs
    from repro.models.config import SHAPES_BY_NAME

    base = sum(len(configs.get(a).shapes) for a in configs.list_archs())
    # mirror launch/dryrun.py::paged_kernel_applicable without importing the
    # module (its XLA_FLAGS device-count spoof must not leak into this
    # process); spec cells share the paged applicability rule
    paged = sum(1 for a in configs.list_archs()
                for s in configs.get(a).shapes
                if SHAPES_BY_NAME[s].kind == "decode"
                and configs.get(a).family in ("dense", "moe", "hybrid"))
    expected = (base + 3 * paged) * 2
    ok = [r for r in rows if r.get("status") == "OK"]
    assert len(rows) == expected == 120
    assert sum(1 for r in rows if r.get("kernel") == "paged") == paged * 2 == 18
    assert sum(1 for r in rows if r.get("kernel") == "spec") == paged * 2 == 18
    assert sum(1 for r in rows
               if r.get("kernel") == "shardmap") == paged * 2 == 18
    assert len(ok) == len(rows), [
        (r["arch"], r["shape"], r.get("error")) for r in rows if r not in ok]


def test_wire_bytes_regression_gate():
    """Every committed matrix cell's wire_bytes_per_device must stay within
    tolerance of the committed baseline — a sharding-rule regression fails
    tier-1 as a named cell (the gate also runs in CI via
    scripts/check_wire_bytes.py on the rebuilt matrix)."""
    matrix = ROOT / "artifacts" / "dryrun_matrix.json"
    baseline = ROOT / "artifacts" / "wire_bytes_baseline.json"
    if not matrix.exists() or not baseline.exists():
        pytest.skip("matrix/baseline not built (scripts/run_matrices.sh, "
                    "scripts/check_wire_bytes.py --update)")
    r = _run([sys.executable, "scripts/check_wire_bytes.py", str(matrix),
              "--baseline", str(baseline)])
    assert r.returncode == 0, r.stdout + r.stderr
    import json
    rows = json.loads(matrix.read_text())
    base = json.loads(baseline.read_text())
    assert f"{len(base)}/{len(base)} cells within" in r.stdout, r.stdout
    # the baseline must cover the whole matrix (new cells get baselined, not
    # silently ungated)
    assert len(base) == len(rows), (
        f"baseline covers {len(base)} of {len(rows)} cells; run "
        "scripts/check_wire_bytes.py --update and commit the diff")


def test_serving_driver():
    from repro.launch.serve import run_serving

    fe = run_serving("whisper-base-smoke", n_requests=6, max_new=3, sessions=2,
                     batch_size=3)
    assert sum(len(v) for v in fe.completions.values()) == 6


def test_quickstart_example():
    r = _run([sys.executable, "examples/quickstart.py"])
    assert r.returncode == 0, r.stderr[-1500:]
    assert "pay-as-you-go bill" in r.stdout


def test_elastic_scaling_example():
    r = _run([sys.executable, "examples/elastic_scaling.py"])
    assert r.returncode == 0, r.stderr[-1500:]
    assert "single system image holds" in r.stdout


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set the entry points set no cache
    dir of their own; without it the cache is the repo's fixed
    ``.jax_cache/``."""
    import jax

    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(compile_cache.ENV)
        assert compile_cache.enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)

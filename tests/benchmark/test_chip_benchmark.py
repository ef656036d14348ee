"""The on-chip serving benchmark's own tests (``benchmarks/chip``), on the CPU.

What runs on the chip is measured there; here the yardstick is checked: the
traffic generator, the tails, the counts of FLOPs and bytes, the trace
reduction on a trace recorded on a v5e, the names in ``BENCHMARK.json``, the
harness finding new files by name, and ``correct`` coming out true for a
sound run and false for a broken one, at a size a test run can hold.
"""

from __future__ import annotations

import gzip
import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import cell, record, roofline, trace, traffic  # noqa: E402
from benchmarks.chip.control import alter_token  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- tiny configurations: the program's ``-smoke`` sizes ---------------------

def tiny_starcoder2():
    return {"name": "starcoder2-tiny", "hidden_act": "gelu_pytorch_tanh",
            "hidden_size": 64, "intermediate_size": 128, "layer_norm_epsilon": 1e-5,
            "norm_type": "layer_norm", "num_attention_heads": 4,
            "num_hidden_layers": 4, "num_key_value_heads": 2,
            "rope_theta": 999999.4420358813, "sliding_window": 4096,
            "use_bias": True, "vocab_size": 256, "tie_word_embeddings": True,
            "program": {"arch": "starcoder2-3b-smoke", "vocab_pad": 256},
            "serving": {"slots": 4, "page_size": 8, "kv_pages": 40,
                        "prefill_chunk": 16, "max_seq": 64},
            # readings at this size, seeds 1-8 on the CPU: served 0 to 0.0067,
            # the fp8 control 0.0198 to 0.0585
            "correctness": {"limits": {"widest_logit_gap": 0.012}}}


def tiny_minicpm():
    return {"name": "minicpm-tiny", "hidden_act": "silu", "hidden_size": 64,
            "intermediate_size": 128, "num_attention_heads": 4,
            "num_hidden_layers": 4, "num_key_value_heads": 4, "rms_norm_eps": 1e-5,
            "rope_theta": 10000.0, "vocab_size": 256, "scale_emb": 12,
            "dim_model_base": 64 * 256 / 2304,
            "scale_depth": 1.4 / math.sqrt(40) * math.sqrt(4),
            "tie_word_embeddings": True,
            "program": {"arch": "minicpm-2b-smoke", "vocab_pad": 256},
            "serving": {"slots": 4, "page_size": 8, "kv_pages": 40,
                        "prefill_chunk": 16, "max_seq": 64},
            # readings at this size, seeds 1-8 on the CPU, as on the chip: the
            # mean separates the fp8 control (served 0 to 5.2e-06, control
            # 2.9e-05 to 8.5e-05), the widest gap an altered token (served 0 to
            # 1.7e-04, control 4.0e-04 to 9.6e-04, every token altered 6.0e-03
            # to 8.6e-03, 95% of single altered tokens above 1.07e-03)
            "correctness": {"limits": {"mean_logit_gap": 1.2e-05,
                                       "widest_logit_gap": 1e-03}}}


def tiny_chat():
    return {"arrival": "poisson", "rate_per_s": 10,
            "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                       "min": 8, "max": 48, "quantum": 8},
            "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 2, "max": 16, "quantum": 2},
            "check_requests": 4}


# a tiny run reports the metrics BENCHMARK.json lists for this cell
CELL = {"name": "minicpm-2b.chat", "config": "tiny", "traffic": "tiny", "chips": 1}


def run_tiny(cfg, *, seed=3, sabotage=None, control=False, spec=None):
    import jax

    from benchmarks.chip import run as harness

    out, run = harness.run_cell(CELL, seed=seed, seconds=2, traced=False,
                                devices=jax.devices(), cfg=cfg,
                                spec=spec or tiny_chat(), sabotage=sabotage,
                                control=control)
    return out, run


def dense_digest(cfg):
    """sha256 of a configuration's weights (every leaf, with its path) and
    of its reference logits over the whole vocabulary at fixed positions,
    in float32 and through the fp8 control."""
    import hashlib

    import jax

    from benchmarks.chip import reference, weights

    dm = cell.dims(cfg)
    arch = cell.program_config(cfg)
    params = weights.make(dm, program_head=not arch.tie_embeddings)(
        *weights.split_seed(2**33 + 5))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    out = {"weights": h.hexdigest()}
    rng = np.random.default_rng(0)
    K, T, N = 2, 64, 24
    tokens = rng.integers(0, dm["vocab"], (K, T), dtype=np.int32)
    pos = np.stack([np.arange(T - N, T), np.arange(10, 10 + N)]).astype(np.int32)
    targets = np.broadcast_to(np.arange(dm["vocab"], dtype=np.int32)[:, None, None],
                              (dm["vocab"], K, N))
    for quant in (None, "fp8"):
        h = hashlib.sha256()
        for a in reference.forward(params, dm, tokens, pos, targets, quant=quant):
            h.update(np.asarray(a).tobytes())
        out[f"reference_{quant or 'f32'}"] = h.hexdigest()
    return out


# -- traffic -------------------------------------------------------------------

@pytest.mark.parametrize("mix", ["chat", "code-complete", "batch-gen"])
def test_traffic_is_fixed_by_seed(mix):
    spec = cell.traffic(mix)
    a = traffic.generate(spec, seed=2**31 + 7, seconds=30, vocab=1000)
    b = traffic.generate(spec, seed=2**31 + 7, seconds=30, vocab=1000)
    c = traffic.generate(spec, seed=12, seconds=30, vocab=1000)
    assert [(r.due, r.max_new, r.prompt.tolist()) for r in a] == \
           [(r.due, r.max_new, r.prompt.tolist()) for r in b]
    # another seed: the same work in another order
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
    assert all(0 <= r.due < 30 for r in a)
    assert [r.due for r in a] == sorted(r.due for r in a)
    for key, reqs in (("prompt", [len(r.prompt) for r in a]),
                      ("output", [r.max_new for r in a])):
        lo, hi = spec[key]["min"], spec[key]["max"]
        assert lo <= min(reqs) and max(reqs) <= hi
    if spec["arrival"] == "poisson":
        assert len(a) == round(spec["rate_per_s"] * 30)
        assert sum(r.due for r in a) != pytest.approx(sum(r.due for r in c))


def test_warm_up_covers_every_shape_of_the_mix():
    spec = cell.traffic("chat")
    sh = traffic.shapes(spec, seconds=30, chunk=256)
    reqs = traffic.generate(spec, seed=5, seconds=30, vocab=100)
    tails = {len(r.prompt) - 256 * ((len(r.prompt) - 1) // 256) for r in reqs}
    assert tails <= set(sh["tails"])
    assert {r.max_new for r in reqs} <= set(sh["outputs"])


# -- tails -----------------------------------------------------------------------

def _run_with_times(times_per_request):
    reqs = {}
    for i, times in enumerate(times_per_request):
        due = times[0] - 0.1 if len(times) else 0.0
        reqs[f"r{i}"] = record.Req(rid=f"r{i}", due=due, prompt_len=8,
                                   max_new=len(times), times=list(times))
    return record.Run(t0=0.0, t1=1e9, setup_s=1.0, requests=reqs,
                      window_requests=list(reqs), steps=[], dm={}, n_pages=1)


def test_tails_are_taken_over_every_sample():
    from benchmarks.chip import run as harness

    rng = np.random.default_rng(0)
    seqs = []
    for _ in range(40):
        gaps = 0.020 + 0.001 * rng.random(49)
        seqs.append(np.concatenate([[1.0], 1.0 + np.cumsum(gaps)]))
    p95 = harness.reader("itl_p95_ms")
    p50 = harness.reader("itl_p50_ms")
    base95, base50 = p95(_run_with_times(seqs)), p50(_run_with_times(seqs))
    assert 20 <= base50 <= base95 <= 21
    # a stall of 300 ms in 8% of the gaps: every request's gap 10..13 grows
    stalled = []
    for s in seqs:
        g = np.diff(s)
        g[10:14] += 0.3
        stalled.append(np.concatenate([[s[0]], s[0] + np.cumsum(g)]))
    assert p95(_run_with_times(stalled)) > 300
    assert p50(_run_with_times(stalled)) == pytest.approx(base50, rel=0.05)
    assert record.tail([1, 2, 3, 4], 0.5) == 2
    assert record.tail(list(range(1, 101)), 0.95) == 95


def test_output_rate_counts_only_tokens_inside_the_window():
    from benchmarks.chip import run as harness

    run = _run_with_times([[0.5, 1.0, 1.5, 2.0, 2.5]] * 4)
    run.t0, run.t1 = 1.0, 2.0
    assert harness.reader("output_tok_s")(run) == pytest.approx(12.0)


# -- FLOPs and bytes against hand counts --------------------------------------------

@pytest.mark.parametrize("name,blocks,head,kv_token", [
    # 40 x (4 x 2304^2 + 3 x 2304 x 5760); 2304 x 122753; 40 x 2 x 36 x 64 x 2
    ("minicpm-2b", 40 * (4 * 2304 ** 2 + 3 * 2304 * 5760), 2304 * 122753,
     40 * 2 * 36 * 64 * 2),
    # 30 x (3072 x (3072 + 2 x 256) + 3072^2 + 2 x 3072 x 12288); 3072 x 49152
    ("starcoder2-3b", 30 * (3072 * 3584 + 3072 ** 2 + 2 * 3072 * 12288),
     3072 * 49152, 30 * 2 * 2 * 128 * 2),
])
def test_decode_flops_and_bytes_match_hand_counts(name, blocks, head, kv_token):
    dm = cell.dims(cell.config(name))
    assert roofline.block_matrix_params(dm) == blocks
    assert roofline.kv_bytes_per_token(dm) == kv_token
    tokens, context = 16, 16 * 700
    attn = 4 * dm["L"] * dm["H"] * dm["hd"] * context
    assert roofline.decode_flops(dm, tokens, context) == \
        tokens * (2 * blocks + 2 * head) + attn
    vectors = roofline.block_vector_params(dm)
    if name == "minicpm-2b":
        assert vectors == 40 * 2 * 2304          # two RMSNorm scales a layer
    else:                                        # LayerNorm scale+bias, biases
        assert vectors == 30 * (4 * 3072 + 3072 + 2 * 256 + 12288 + 3072)
    final = dm["d"] * (2 if dm["norm"] == "layernorm" else 1)
    assert roofline.decode_bytes(dm, tokens, context) == \
        2 * (blocks + vectors + final + head) + kv_token * context + tokens * dm["d"] * 2
    peak = roofline.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    # a decode step at this size is bound by bandwidth
    f = roofline.decode_flops(dm, tokens, context)
    b = roofline.decode_bytes(dm, tokens, context)
    assert roofline.least_seconds(f, b, peak) == b / 819e9


def test_an_unknown_chip_has_no_peaks():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_a_mesh_reads_its_work_against_all_its_chips():
    """On four chips the decode step's roofline share and MFU read a
    quarter of what the same trace and steps read against one chip."""
    from benchmarks.chip import run as harness

    with gzip.open(DATA / "v5e_starcoder2_code_trace.json.gz", "rt") as f:
        tr = json.load(f)
    run = record.Run(t0=0.0, t1=1.0, setup_s=1.0, requests={}, window_requests=[],
                     steps=[record.Step(t=0.5, decode_tokens=16, context=16 * 700)],
                     dm=cell.dims(cell.config("starcoder2-3b")), n_pages=1, trace=tr)
    one, four = roofline.peaks("TPU v5 lite"), roofline.peaks("TPU v5 lite", 4)
    assert four["bf16_flops_per_s"] == 4 * one["bf16_flops_per_s"]
    assert four["hbm_bytes_per_s"] == 4 * one["hbm_bytes_per_s"]
    for name in ("decode_roofline", "decode_mfu"):
        run.peak = one
        a = harness.reader(name)(run)
        run.peak = four
        b = harness.reader(name)(run)
        assert 0 < a <= 100 and b == pytest.approx(a / 4, rel=1e-12)


@pytest.mark.parametrize("name", ["minicpm-tiny", "starcoder2-tiny"])
def test_the_dense_block_file_keeps_weights_and_reference_bit_for_bit(name):
    """The dense block's weights and reference logits, now from
    ``blocks/dense.py``, are those the harness gave before the block moved
    there: the digest in ``data/`` was taken from that code."""
    want = json.loads((DATA / "dense_block_digest.json").read_text())[name]
    make = {"minicpm-tiny": tiny_minicpm, "starcoder2-tiny": tiny_starcoder2}[name]
    assert dense_digest(make()) == want


# -- trace reduction ---------------------------------------------------------------

def test_trace_reduction_on_a_recorded_v5e_trace():
    """A slice of a trace recorded on one v5e while
    ``starcoder2-3b.code-complete`` ran (``trace.load`` output).  Busy time
    and gaps are checked against a brute-force reading on a 1-microsecond
    grid."""
    with gzip.open(DATA / "v5e_starcoder2_code_trace.json.gz", "rt") as f:
        tr = json.load(f)
    lo, hi = tr["window"]
    ops = next(iter(tr["devices"].values()))["ops"]
    grid = np.zeros(int(round((hi - lo) * 1e6)) + 1, bool)
    for _, s, e in ops:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int(round((a - lo) * 1e6)):int(round((b - lo) * 1e6))] = True
    assert trace.busy_s(tr) == pytest.approx(grid.sum() * 1e-6, abs=2e-5 * len(ops))
    assert trace.window_s(tr) == pytest.approx(hi - lo)
    dec = trace.program_times(tr, trace.DECODE_PROGRAM)
    assert len(dec) == 1 and 0.02 < dec[0] < 0.05
    (_, s, e), = [m for m in next(iter(tr["devices"].values()))["modules"]
                  if m[0].startswith("jit__step_impl")]
    assert dec[0] == pytest.approx(e - s)
    gaps = trace.idle_gaps(tr)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert sum(g for _, g in gaps) <= (hi - lo) - trace.busy_s(tr) + 1e-9
    assert {n for n, _ in gaps} <= set(trace.SPANS) | {"other"}
    top = trace.top_ops(tr)
    assert len(top) <= 10 and top == sorted(top, key=lambda o: -o[1])


def test_union_merges_overlaps():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]


# -- BENCHMARK.json --------------------------------------------------------------------

def test_benchmark_json_names_units_and_files():
    b = cell.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    names = [c["name"] for c in b["configs"]] + [w["name"] for w in b["workloads"]] \
        + [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in b["workloads"]] + \
            [k for c in b["configs"] for k in c["reduced"]]:
        assert NAME.match(n), n
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert (ROOT / "benchmarks/chip/metrics" / f"{m['name']}.py").exists()
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    for c in b["configs"]:
        assert (ROOT / c["file"]).exists() and c["file"].startswith("benchmarks/chip/")
        assert cell.config(c["name"])["reduced"] == c["reduced"]
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell.config(w["config"])
        cell.traffic(w["traffic"])
        # every cell reports setup_s, another end-to-end metric and a per-layer one
        e = {m["name"] for m in cell.metrics_for(b, w["name"], traced=False)}
        assert "setup_s" in e and len(e) >= 2
        assert cell.metrics_for(b, w["name"], traced=True)
    for p in b["paths"]:
        assert (ROOT / p).is_dir() and re.match(r"^[A-Za-z0-9_./\-]{1,200}$", p)
    assert len(json.dumps(b)) < 64 * 1024


@pytest.mark.parametrize("name", ["minicpm-2b", "starcoder2-3b"])
def test_configuration_is_the_programs_at_published_widths(name):
    import jax

    from repro.models import build_model
    from repro.models.layers import to_compute_dtype

    from benchmarks.chip import weights

    cfg = cell.config(name)
    dm = cell.dims(cfg)
    arch = cell.program_config(cfg)
    want = jax.eval_shape(lambda k: to_compute_dtype(build_model(arch).init(k)),
                          jax.random.key(0))
    have = jax.eval_shape(weights.make(dm, program_head=not arch.tie_embeddings),
                          *weights.split_seed(0))
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(have)
    assert [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(want)] == \
           [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(have)]
    s = cfg["serving"]
    assert s["max_seq"] % s["page_size"] == 0 and s["kv_pages"] >= s["max_seq"] // s["page_size"]


def test_cli_without_a_tpu_exits_2_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload",
                        "minicpm-2b.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 2 and p.stdout == ""


# -- correctness ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def sound_run():
    return run_tiny(tiny_starcoder2())


def test_a_sound_run_is_correct(sound_run):
    out, run = sound_run
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 20
    assert out["checks"]["checked_requests"]["value"] == 4
    assert set(out["metrics"]) == {"itl_p50_ms", "itl_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    longest = max(len(r.tokens) for r in run.finished)
    assert longest >= 8


def test_the_control_fails_the_limit():
    """The fp8 reference in the program's place is not correct: it reads a
    widest gap over the limit that the bf16 program keeps, at this size as
    on the chip."""
    out, run = run_tiny(tiny_starcoder2(), control=True)
    c = out["checks"]
    assert out["correct"] is False
    assert c["widest_logit_gap"]["value"] > c["widest_logit_gap"]["limit"]
    assert run.gaps["widest_logit_gap"] <= c["widest_logit_gap"]["limit"]


def test_the_control_fails_minicpms_mean_gap():
    """minicpm's flat logits leave the fp8 control's widest gap under its
    limit; its mean gap is what fails, at this size as on the chip."""
    out, run = run_tiny(tiny_minicpm(), seed=4, control=True)
    c = out["checks"]
    assert out["correct"] is False
    assert c["mean_logit_gap"]["value"] > c["mean_logit_gap"]["limit"]
    assert c["widest_logit_gap"]["value"] <= c["widest_logit_gap"]["limit"]
    assert run.gaps["mean_logit_gap"] <= c["mean_logit_gap"]["limit"]


def test_a_token_altered_where_it_is_produced_is_not_correct():
    out, _ = run_tiny(tiny_starcoder2(), sabotage=alter_token)
    assert out["correct"] is False
    c = out["checks"]["widest_logit_gap"]
    assert c["value"] > c["limit"]


def test_an_altered_token_fails_minicpms_widest_gap():
    out, _ = run_tiny(tiny_minicpm(), seed=4, sabotage=alter_token)
    assert out["correct"] is False
    c = out["checks"]["widest_logit_gap"]
    assert c["value"] > c["limit"]


def test_minicpm_block_is_correct():
    out, _ = run_tiny(tiny_minicpm(), seed=4)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"mean_logit_gap", "widest_logit_gap",
                                  "bad_requests", "checked_requests"}


# a block of its own, as a later configuration brings it: the dense block with
# Qwen3's per-head RMSNorm on q and k before the rope (``q_norm``/``k_norm``,
# float32 leaves of ``head_dim`` in the program)
QWEN3_BLOCK = '''
import jax
import jax.numpy as jnp

from .. import reference as ref
from . import dense

dims = dense.dims
matrix_params = dense.matrix_params


def check_program(arch, dm):
    bad = dense.check_program(arch, dm)
    if not arch.qk_norm:
        bad["qk_norm"] = (True, False)
    return bad


def layer_leaves(dm):
    return dense.layer_leaves(dm) + [
        (("attn", "q_norm"), (dm["hd"],), "scale", jnp.float32),
        (("attn", "k_norm"), (dm["hd"],), "scale", jnp.float32)]


def vector_params(dm):
    return dense.vector_params(dm) + 2 * dm["hd"]


def head_norm(x, scale, dm):
    ms = (x * x).mean(-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + dm["eps"]) * scale


def block(x, lp, dm, quant):
    a = lp["attn"]
    q, k, v = dense.qkv(ref.norm(x, lp["attn_norm"], dm), a, dm, quant)
    q, k = head_norm(q, a["q_norm"], dm), head_norm(k, a["k_norm"], dm)
    x = x + ref.mm(dense.attend(q, k, v, dm, quant), a["wo"], quant) * dm["residual_scale"]
    h = ref.norm(x, lp["mlp_norm"], dm)
    return x + dense.mlp(h, lp["mlp"], dm, quant) * dm["residual_scale"]
'''


def tiny_qwen3():
    return {"name": "qwen3-tiny", "block": "qwen3", "hidden_act": "silu",
            "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
            "num_hidden_layers": 4, "num_key_value_heads": 2, "head_dim": 16,
            "rms_norm_eps": 1e-6, "rope_theta": 1e6, "vocab_size": 256,
            "tie_word_embeddings": False,
            "program": {"arch": "qwen3-14b-smoke", "vocab_pad": 256},
            "serving": {"slots": 4, "page_size": 8, "kv_pages": 40,
                        "prefill_chunk": 16, "max_seq": 64},
            # readings at this size, seeds 1-8 on the CPU: served widest 0 to
            # 0.043, mean 0 to 1.24e-03; the fp8 control widest 0.082 to 0.65,
            # mean 4.7e-03 to 0.068; every token altered widest 4.1 to 5.5, 99%
            # of single altered tokens above 0.57
            "correctness": {"limits": {"mean_logit_gap": 2.5e-03,
                                       "widest_logit_gap": 0.25}}}


def test_new_configuration_traffic_and_metric_are_found_by_name(tmp_path):
    """Copy the benchmark, add configurations, a block, a traffic mix and a
    metric as new files with their entries, and run the new cells: nothing
    else is edited.  The block's cell is correct, and not correct with
    every token altered."""
    dst = tmp_path / "benchmarks" / "chip"
    shutil.copytree(ROOT / "benchmarks" / "chip", dst,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (dst / "configs" / "tiny-new.json").write_text(json.dumps(tiny_minicpm()))
    (dst / "configs" / "tiny-qwen3.json").write_text(json.dumps(tiny_qwen3()))
    (dst / "blocks" / "qwen3.py").write_text(QWEN3_BLOCK)
    (dst / "traffic" / "tiny-burst.json").write_text(json.dumps(tiny_chat()))
    (dst / "metrics" / "requests_done.py").write_text(
        "def read(run):\n    return float(len(run.finished))\n")
    b = cell.benchmark()
    cells = ["tiny-new.burst", "tiny-qwen3.burst"]
    for name in ("tiny-new", "tiny-qwen3"):
        b["configs"].append({"name": name, "source": "test", "reduced": [],
                             "file": f"benchmarks/chip/configs/{name}.json",
                             "why": "test"})
        b["workloads"].append({"name": f"{name}.burst", "config": name,
                               "traffic": "tiny-burst", "chips": 1, "why": "test"})
    b["end_to_end"].append({"name": "requests_done", "unit": "requests",
                            "better": "higher", "bound": 0.1, "source": "host_clock",
                            "workloads": cells})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    code = ("import json, sys, jax; sys.path[:0] = [sys.argv[1], sys.argv[2]]\n"
            "from benchmarks.chip import cell, run\n"
            "from benchmarks.chip.control import alter_token\n"
            "for name, fault in [('tiny-new.burst', None), ('tiny-qwen3.burst', None),\n"
            "                    ('tiny-qwen3.burst', alter_token)]:\n"
            "    w = cell.workload(cell.benchmark(), name)\n"
            "    out, _ = run.run_cell(w, seed=2, seconds=2, traced=False, "
            "devices=jax.devices(), sabotage=fault)\n"
            "    print(json.dumps(out))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code, str(tmp_path), str(ROOT / "src")],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    new, qwen3, altered = (json.loads(x) for x in p.stdout.strip().splitlines()[-3:])
    for out in (new, qwen3):
        assert out["correct"], out["checks"]
        assert out["metrics"]["requests_done"]["value"] > 0
        # metrics listed for the other cells only stay out
        assert set(out["metrics"]) == {"requests_done", "setup_s"}
    assert altered["correct"] is False
    c = altered["checks"]["widest_logit_gap"]
    assert c["value"] > c["limit"]


# -- a cell on a mesh ------------------------------------------------------------------

MESH_RUN = """
import json, sys
sys.path[:0] = sys.argv[1:4]
import jax
from test_chip_benchmark import CELL, tiny_chat, tiny_minicpm
from benchmarks.chip import check, run
from benchmarks.chip.control import alter_token
from repro.dist.sharding import param_shardings

cfg = tiny_minicpm()
cfg["serving"]["mesh"] = {"data": 1, "model": 4}
w = dict(CELL, chips=4)
seen = {}


def placed(sched):
    want = param_shardings(sched.params, sched._mesh)
    seen["program"] = all(a.sharding == b for a, b in zip(
        jax.tree_util.tree_leaves(sched.params), jax.tree_util.tree_leaves(want),
        strict=True))


forward = check.reference.forward


def spread(params, *a, **k):
    seen["reference"] = sorted(
        (len(x.sharding.device_set), x.addressable_shards[0].data.size * 4 // x.size)
        for x in jax.tree_util.tree_leaves(params))
    return forward(params, *a, **k)


check.reference.forward = spread
for fault in (placed, alter_token):
    out, _ = run.run_cell(w, seed=3, seconds=2, traced=False, devices=jax.devices(),
                          cfg=cfg, spec=tiny_chat(), sabotage=fault)
    print(json.dumps(dict(out, seen=seen)))
"""


def test_a_four_chip_cell_runs_on_its_mesh():
    """The tiny minicpm cell on four (virtual CPU) chips with
    ``serving.mesh``: the weights are made in the program's placement and
    stay there, the reference runs over the same four chips with every
    leaf split four ways, the run is correct, and an altered token is
    not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", MESH_RUN, str(ROOT), str(ROOT / "src"),
                        str(Path(__file__).resolve().parent)],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    sound, altered = (json.loads(x) for x in p.stdout.strip().splitlines()[-2:])
    assert sound["correct"], sound["checks"]
    assert sound["device"]["count"] == 4
    assert sound["seen"]["program"] is True
    # every reference leaf lies on the four chips, a quarter on each
    assert set(map(tuple, sound["seen"]["reference"])) == {(4, 1)}
    assert altered["correct"] is False
    c = altered["checks"]["widest_logit_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("mesh,chips", [({"data": 1, "model": 2}, 4), (None, 4),
                                        ({"data": 1, "model": 4}, 4)])
def test_a_mesh_that_does_not_fit_the_cell_is_refused(mesh, chips):
    """A mesh whose size is not the cell's chips, a cell on several chips
    with no mesh, and a mesh larger than the devices given."""
    import jax

    cfg = tiny_minicpm()
    if mesh is not None:
        cfg["serving"]["mesh"] = mesh
    with pytest.raises(cell.CellError):
        cell.mesh(cfg, jax.devices()[:1], chips)

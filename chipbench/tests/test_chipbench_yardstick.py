"""CPU tests of the benchmark's yardstick: traffic generation, the
counting functions, the reference's Eq 17, and discovery by name."""
from __future__ import annotations

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import counts, harness, traffic  # noqa: E402

BIG_SEED = 2 ** 31 + 12345


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark(ROOT)


# ------------------------------------------------------------ traffic


@pytest.mark.parametrize("name", ["batch", "chat"])
def test_length_sets_within_clip(name):
    mix = harness.load_data("traffic", name)
    for key in ("prompt_tokens", "output_tokens"):
        spec = mix[key]
        lens = traffic.length_set(spec, 500)
        assert lens.min() >= spec["min"] and lens.max() <= spec["max"]
        assert abs(np.median(lens) - spec["median"]) <= 1
    assert mix["prompt_tokens"]["max"] <= mix["max_prompt"]
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            <= mix["max_seq"])


def _stream(seed, n=300):
    mix = harness.load_data("traffic", "batch")
    st = traffic.ClosedStream(mix, seed, 32064)
    return [st.next() for _ in range(n)]


def test_closed_stream_deterministic_per_seed():
    a, b, c = _stream(BIG_SEED), _stream(BIG_SEED), _stream(BIG_SEED + 1)
    assert all(np.array_equal(x.prompt, y.prompt)
               and x.max_tokens == y.max_tokens for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_closed_stream_same_sizes_every_seed():
    """Each pass over the set holds the same sizes, in a seeded order."""
    mix = harness.load_data("traffic", "batch")
    n = mix["set_size"]
    for seed in (0, BIG_SEED):
        reqs = _stream(seed, n)
        assert sorted(len(r.prompt) for r in reqs) == sorted(
            traffic.length_set(mix["prompt_tokens"], n).tolist())
        assert sorted(r.max_tokens for r in reqs) == sorted(
            traffic.length_set(mix["output_tokens"], n).tolist())
        assert all(r.prompt.min() >= 0 and r.prompt.max() < 32064
                   for r in reqs)


def test_open_schedule_fixed_work_seeded_order():
    mix = harness.load_data("traffic", "chat")
    a = traffic.open_schedule(mix, BIG_SEED, 30.0, 32064)
    b = traffic.open_schedule(mix, BIG_SEED, 30.0, 32064)
    c = traffic.open_schedule(mix, 7, 30.0, 32064)
    assert len(a) == len(c) == int(np.ceil(mix["rate_per_s"] * 30.0))
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in c)
    assert a[-1].arrival_s == pytest.approx(c[-1].arrival_s)
    assert [r.arrival_s for r in a] != [r.arrival_s for r in c]
    assert all(x < y for x, y in zip([r.arrival_s for r in a],
                                     [r.arrival_s for r in a][1:]))
    # The gaps' set has the mean of the Poisson rate.
    assert a[-1].arrival_s / len(a) == pytest.approx(
        1.0 / mix["rate_per_s"], rel=0.05)


# ------------------------------------------------------------ counts


def test_cim_mvm_counts_by_hand():
    # M=64, I=3072, N=8192, 8-bit codes on 64x64 tiles (8 weights a row).
    assert counts.cim_mvm_flops(64, 3072, 8192) == 2 * 64 * 3072 * 8192
    b = counts.cim_mvm_bytes(64, 3072, 8192, n_bits=8, rows=64, cols=64,
                             gain=False)
    codes = 3072 * 8192 * 9 / 8
    positions = 3072 * 1024          # one byte per tile row
    acts = 2 * 64 * 3072 + 2 * 64 * 8192
    assert b == codes + positions + acts
    g = counts.cim_mvm_bytes(64, 3072, 8192, n_bits=8, rows=64, cols=64,
                             gain=True)
    assert g - b == 4 * 3072 * 8192
    # A partial column tile still takes a whole tile row of positions.
    assert counts.cim_mvm_bytes(1, 64, 9, n_bits=8, rows=64, cols=64,
                                gain=False) == 64 * 9 * 9 / 8 + 64 * 2 \
        + 2 * 64 + 2 * 9


def test_least_seconds_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_seconds(1000.0, 10.0, peak) == (10.0, "compute")
    assert counts.least_seconds(10.0, 1000.0, peak) == (100.0, "memory")


def test_token_and_prefill_flops_by_hand():
    cfg = harness.load_data("configs", "phi3-mini-3.8b.ideal")
    s = counts.dense_shapes(cfg)
    w = 4 * 3072 * 3072 + 3 * 3072 * 8192          # MHA: 32 KV heads
    assert counts.matmul_weights_per_layer(s) == w
    head = 2 * 3072 * 32064
    layers = 3                                      # as deployed here
    assert s["layers"] == layers
    assert counts.token_flops(s, 100, sampled=True) == \
        layers * (2 * w + 4 * 100 * 32 * 96) + head
    assert counts.token_flops(s, 100, sampled=False) == \
        layers * (2 * w + 4 * 100 * 32 * 96)
    # A prompt: every position attends to itself and all before it.
    L = 7
    by_token = sum(counts.token_flops(s, i + 1, sampled=(i == L - 1))
                   for i in range(L))
    assert counts.prefill_flops(s, L) == by_token
    # The window caps the context attended.
    s_w = dict(s, window=4)
    by_token = sum(counts.token_flops(s_w, i + 1, sampled=(i == L - 1))
                   for i in range(L))
    assert counts.prefill_flops(s_w, L) == by_token


# ------------------------------------------------------------ discovery


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_finds_its_file(bench):
    for c in bench["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        cfg = harness.load_data("configs", c["name"])
        assert cfg["name"] == c["name"]
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "references", cfg["reference"] + ".py"))
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert callable(harness.load_module(
            "devices", cfg["devices"]["model"]).engine_kwargs)
    for w in bench["workloads"]:
        mix = harness.load_data("traffic", w["traffic"])
        assert mix["name"] == w["traffic"]
        loop = harness.load_module("loops", mix["loop"])
        assert callable(loop.prepare) and callable(loop.drive)
        assert any(c["name"] == w["config"] for c in bench["configs"])
    for m in bench["end_to_end"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
    for m in bench["per_layer"]:
        assert callable(harness.load_module("layer_metrics",
                                            m["name"]).read)


def test_benchmark_file_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert "bound" not in m
        for w in m["workloads"]:
            reported = [x["name"] for x in harness.metrics_of(bench, w,
                                                              False)]
            assert m["moves"] in reported
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        e = harness.metrics_of(bench, w["name"], False)
        assert "setup_s" in [m["name"] for m in e] and len(e) >= 2
        assert harness.metrics_of(bench, w["name"], True)
    del layers


@pytest.mark.parametrize("change", [
    {"rope_scaling": {"type": "longrope"}},
    {"cim": {"sigma_read": 0.01}},
    {"hidden_act": "gelu_new"},
    {"tie_word_embeddings": True},
])
def test_model_config_refuses_what_it_cannot_map(change):
    """No key of a configuration file is dropped in silence."""
    from chipbench import program

    cfg = harness.load_data("configs", "phi3-mini-3.8b.ideal")
    program.model_config(cfg)
    for key, value in change.items():
        cfg[key] = ({**cfg[key], **value} if key in cfg
                    and isinstance(value, dict) else value)
    with pytest.raises(ValueError, match="phi3-mini"):
        program.model_config(cfg)


def test_ideal_devices_take_no_settings():
    ideal = harness.load_module("devices", "ideal")
    assert ideal.engine_kwargs({"model": "ideal"}) == {}
    with pytest.raises(ValueError):
        ideal.engine_kwargs({"model": "ideal", "sigma_program": 0.03})


def test_metrics_of_selects_by_cell(bench):
    batch = [m["name"] for m in harness.metrics_of(bench,
                                                   "phi3-ideal.batch", True)]
    assert "cim_mvm_roofline.batch" in batch
    assert "admit_ms.chat" not in batch
    chat = [m["name"] for m in harness.metrics_of(bench, "phi3-ideal.chat",
                                                  False)]
    assert chat == ["setup_s", "itl_p95_ms"]


def test_peaks_keyed_by_device_kind():
    with open(os.path.join(ROOT, "chipbench", "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]


# ------------------------------------------------------------ reference


def test_reference_eq17_matches_program_at_small_size():
    """The reference's own bit-slicing, MDM plan and Eq 17 agree with the
    program's ``noisy_weights`` on a seeded matrix."""
    import jax
    import jax.numpy as jnp

    from repro.core.noise import noisy_weights
    from repro.core.tiling import CrossbarSpec

    ref = harness.load_module("references", "dense_gqa_cim")
    w = (jax.random.normal(jax.random.PRNGKey(3), (200, 52)) * 0.05
         ).astype(jnp.bfloat16).astype(jnp.float32)
    ours = ref.eq17(w, rows=64, cols=64, n_bits=8, eta=2e-3)
    theirs, _ = noisy_weights(w, CrossbarSpec(64, 64, 8), "mdm", 2e-3)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               rtol=0, atol=1e-6 * float(jnp.abs(w).max()))
    clean = ref.eq17(w, rows=64, cols=64, n_bits=8, eta=0.0)
    assert float(jnp.abs(ours - clean).max()) > 1e-3 * float(
        jnp.abs(w).max())


def test_reference_weights_deterministic_any_seed():
    cfg = dict(harness.load_data("configs", "phi3-mini-3.8b.ideal"),
               hidden_size=32, num_attention_heads=2,
               num_key_value_heads=2, head_dim=16, intermediate_size=64,
               vocab_size=200)
    ref = harness.load_module("references", "dense_gqa_cim")
    a = ref.make_weights(cfg, BIG_SEED)
    b = ref.make_weights(cfg, BIG_SEED)
    c = ref.make_weights(cfg, 5)
    assert a["embed"].shape == (256, 32)
    assert a["slot0_attn"]["wq"].shape == (cfg["num_hidden_layers"], 32,
                                            2, 16)
    assert str(a["embed"].dtype) == "bfloat16"
    assert np.array_equal(np.asarray(a["lm_head"], np.float32),
                          np.asarray(b["lm_head"], np.float32))
    assert not np.array_equal(np.asarray(a["lm_head"], np.float32),
                              np.asarray(c["lm_head"], np.float32))

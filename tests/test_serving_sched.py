"""Continuous-batching serving tier: scheduler policy, the slot-pool
recompilation guarantee, per-sequence determinism, and hot-swap
atomicity under load.

Contracts under test:

(a) ``RequestScheduler`` is strict-FIFO admission with validated
    submissions, correct live/finished bookkeeping, and streaming
    callbacks that fire once per sampled token with the done edge.
(b) ``SlotPool`` joins a prefilled sequence by index update — the slot
    clock takes the *true* prompt length and the padding tail of the
    fixed-shape prefill is masked to ``EMPTY_POS`` — and eviction
    self-masks the slot; neither changes a shape.
(c) ``ContinuousEngine`` at temperature 0 is token-identical to
    ``ServeEngine.generate``; per-request outputs are bit-deterministic
    across batch compositions, slot placement, staggered admission and
    mixed temperatures (the ``fold_in(PRNGKey(seed), n)`` schedule);
    and the decode/prefill/join/evict lowerables each compile exactly
    once per engine across all that churn.
(d) Hot swaps are atomic under the scheduler: a mid-load async
    redeploy (and a heal-driven epoch swap) leaves in-flight sequences
    bit-identical to a swap-free twin, lands with zero failed
    requests, and new admissions serve exactly the new bank.
(e) ``sample_tokens`` with an *array* temperature is a runtime operand
    (mixed temperatures, one trace) that agrees with the historical
    float path row-wise.
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import CimConfig, ModelConfig
from repro.models.attention import EMPTY_POS
from repro.nonideal import NonidealModel
from repro.serve import (
    ContinuousEngine,
    RequestScheduler,
    ServeEngine,
    SlotPool,
    sample_tokens,
)

VOCAB = 128


def _cfg(cim: bool = False) -> ModelConfig:
    return ModelConfig(
        name="cim-serving-sched", n_layers=2, d_model=32, n_heads=2,
        n_kv_heads=2, d_ff=64, vocab_size=VOCAB,
        block_pattern=("attn",), remat="none", dtype="float32",
        attn_chunk=32,
        cim=CimConfig(enabled=cim, mode="mdm", rows=16, cols=16,
                      n_bits=4))


def _params(cfg, seed: int = 0):
    from repro.models.model import init_params
    return init_params(cfg, jax.random.PRNGKey(seed))


def _prompts(n, length=8, seed=5):
    rs = np.random.RandomState(seed)
    return rs.randint(0, VOCAB, size=(n, length)).astype(np.int32)


# --------------------------- scheduler policy -----------------------------


def test_scheduler_fifo_admission_and_bookkeeping():
    s = RequestScheduler()
    rids = [s.submit(np.array([1, 2, 3]), max_tokens=2) for _ in range(3)]
    assert rids == [0, 1, 2]
    assert s.queue_depth == 3 and s.pending == 3
    first = s.pop_admission()
    assert first.rid == 0                    # strict FIFO
    s.start(first, slot=1, epoch=0)
    assert s.pending == 3                    # 2 queued + 1 live
    with pytest.raises(ValueError):          # occupied slot
        s.start(s.pop_admission(), slot=1, epoch=0)
    assert not s.record_token(1, 7)          # 1/2 tokens: not done
    assert s.record_token(1, 9)              # 2/2: budget hit
    seq = s.finish(1)
    assert seq.tokens == [7, 9]
    assert s.results[0] == [7, 9]
    assert 1 not in s.live


def test_scheduler_validates_submissions():
    s = RequestScheduler()
    with pytest.raises(ValueError):
        s.submit(np.array([], np.int32), max_tokens=1)
    with pytest.raises(ValueError):
        s.submit(np.array([1]), max_tokens=0)


def test_scheduler_streams_tokens_with_done_edge():
    s = RequestScheduler()
    seen = []
    rid = s.submit(np.array([1]), max_tokens=2,
                   on_token=lambda r, t, d: seen.append((r, t, d)))
    s.start(s.pop_admission(), slot=0, epoch=0)
    s.record_token(0, 11)
    s.record_token(0, 12)
    assert seen == [(rid, 11, False), (rid, 12, True)]


# ----------------------------- slot pool ----------------------------------


def test_slot_pool_join_masks_padding_and_evict_self_masks():
    cfg = _cfg()
    pool = SlotPool(cfg, capacity=3, max_seq=16)
    slot_names = [k for k in pool.state if k != "pos"]
    st = pool.fresh_seq_state()
    # Simulate a prefill that wrote positions 0..15 into the B=1 cache.
    for name in slot_names:
        st[name]["kpos"] = jnp.broadcast_to(
            jnp.arange(16, dtype=jnp.int32),
            st[name]["kpos"].shape).astype(jnp.int32)
    assert pool.acquire() == 0               # lowest-free policy
    pool.join(0, st, length=5)
    pos = np.asarray(pool.state["pos"])
    assert pos[0] == 5 and pos[1] == 0
    kp = np.asarray(pool.state[slot_names[0]]["kpos"])[:, 0]
    # True prompt entries keep their positions; the padding tail the
    # fixed-shape prefill wrote is masked out of attention's view.
    assert np.array_equal(kp[:, :5],
                          np.broadcast_to(np.arange(5), kp[:, :5].shape))
    assert np.all(kp[:, 5:] == EMPTY_POS)
    pool.evict(0)
    assert np.asarray(pool.state["pos"])[0] == 0
    assert np.all(
        np.asarray(pool.state[slot_names[0]]["kpos"])[:, 0] == EMPTY_POS)
    assert pool.n_free == 3
    assert pool.traces == {"join": 1, "evict": 1, "merge": 0}


# ------------------------ engine determinism ------------------------------


def test_engine_greedy_matches_serve_engine():
    """Capacity-2 continuous decode == the single-batch reference."""
    cfg = _cfg()
    params = _params(cfg)
    prompts = _prompts(1)
    ref = np.asarray(ServeEngine(cfg, params, max_seq=64)
                     .generate(jnp.asarray(prompts), 8))[0]
    eng = ContinuousEngine(cfg, params, capacity=2, max_seq=64,
                           max_prompt=16)
    rid = eng.submit(prompts[0], max_tokens=8)
    out = eng.run()[rid]
    assert out == list(ref)


def test_composition_determinism_and_single_trace():
    """Per-request outputs don't depend on batchmates, admission order
    or slot placement; all the churn shares one trace per lowerable."""
    cfg = _cfg()
    params = _params(cfg)
    prompts = _prompts(4)
    temps = (0.0, 0.9, 1.3, 0.7)

    def alone(i):
        eng = ContinuousEngine(cfg, params, capacity=3, max_seq=64,
                               max_prompt=16)
        rid = eng.submit(prompts[i], max_tokens=6, temperature=temps[i],
                         seed=40 + i)
        return eng.run()[rid]

    solo = [alone(i) for i in range(4)]

    eng = ContinuousEngine(cfg, params, capacity=3, max_seq=64,
                           max_prompt=16)
    rids = [eng.submit(prompts[i], max_tokens=6, temperature=temps[i],
                       seed=40 + i) for i in range(2)]
    eng.step()                               # stagger: 2 in flight...
    rids += [eng.submit(prompts[i], max_tokens=6, temperature=temps[i],
                        seed=40 + i) for i in range(2, 4)]
    crowd = eng.run()
    for i, rid in enumerate(rids):
        assert crowd[rid] == solo[i], f"request {i} not bit-identical"
    assert eng.traces == {"prefill": 1, "decode": 1}
    assert eng.pool.traces["join"] == 1 and eng.pool.traces["evict"] == 1


def test_sample_tokens_array_temperature_matches_float_path():
    key = jax.random.PRNGKey(3)
    logits = jax.random.normal(jax.random.PRNGKey(4), (4, VOCAB))
    greedy = np.asarray(sample_tokens(logits, key, 0.0))
    hot = np.asarray(sample_tokens(logits, key, 0.8))
    mixed = np.asarray(sample_tokens(logits, key,
                                     jnp.array([0.0, 0.8, 0.0, 0.8])))
    assert np.array_equal(mixed[[0, 2]], greedy[[0, 2]])
    assert np.array_equal(mixed[[1, 3]], hot[[1, 3]])
    # Runtime operand: sweeping the temperature reuses one trace.
    traces = {"n": 0}

    def counted(lg, k, t):
        traces["n"] += 1
        return sample_tokens(lg, k, t)

    f = jax.jit(counted)
    for t in (0.0, 0.5, 1.5):
        f(logits, key, jnp.full((4,), t))
    assert traces["n"] == 1


# ------------------------- hot-swap atomicity -----------------------------


@pytest.mark.parametrize("swap", ["redeploy", "heal"])
def test_hot_swap_mid_load_atomicity(swap):
    """A mid-load bank swap never perturbs in-flight sequences.

    Twin engines serve the identical in-flight group; one takes a bank
    swap mid-decode (async redeploy to a second checkpoint, or a
    heal-driven aging restack), the other serves swap-free.  In-flight
    outputs must match bit-for-bit, every request must finish, and —
    for the redeploy — an admission after the swap must match a fresh
    engine deployed directly on the new checkpoint.
    """
    from repro.deploy import PlanCache
    from repro.health import DetectorConfig, HealthConfig

    cfg = _cfg(cim=True)
    params = _params(cfg)
    model = NonidealModel(drift_nu=0.05, sigma_program=0.02)
    health = (HealthConfig(n_probes=8,
                           detector=DetectorConfig(warmup=3))
              if swap == "heal" else None)
    prompts = _prompts(2, seed=9)

    with tempfile.TemporaryDirectory() as tmp:
        def engine(p):
            return ContinuousEngine(cfg, p, capacity=2, max_seq=64,
                                    max_prompt=16,
                                    plan_cache=PlanCache(tmp),
                                    nonideal=model, health=health)

        def fly(eng):
            rids = [eng.submit(prompts[i], max_tokens=6,
                               temperature=0.5 * i, seed=60 + i)
                    for i in range(2)]
            eng.step()                       # both in flight, epoch 0
            return rids

        ref = engine(params)
        ref_out = [ref.run()[r] for r in fly(ref)]

        eng = engine(params)
        rids = fly(eng)
        if swap == "redeploy":
            params2 = _params(cfg, seed=1)
            t = eng.begin_redeploy(params2)
            eng.run()
            t.join()
            eng.step()                       # install if not yet landed
        else:
            eng.advance(10.0)                # aging restack -> new epoch
            eng.run()
        assert eng.serving_epoch > 0
        out = [eng.results[r] for r in rids]
        assert out == ref_out                # in-flight: bit-identical
        assert all(len(t) == 6 for t in out)
        assert eng.traces["decode"] == 1     # epoch fan-out: same trace

        if swap == "redeploy":
            g2 = _prompts(1, seed=13)[0]
            rid = eng.submit(g2, max_tokens=6, temperature=0.7, seed=99)
            post = eng.run()[rid]
            fresh = engine(params2)
            rid_f = fresh.submit(g2, max_tokens=6, temperature=0.7,
                                 seed=99)
            assert post == fresh.run()[rid_f]


@pytest.mark.parametrize("to", ["nonideal", "ideal"])
def test_redeploy_to_another_tree_keeps_the_pool_layout(to):
    """A redeploy whose deployment tree differs from the serving bank's
    (a nonideal model folds a per-weight ``gain`` into every deployment;
    going back to ideal devices drops it) gets a decode of its own, held
    to the pool's format: no step raises, the pool keeps its format,
    in-flight sequences (decoded beside a post-swap admission, on a
    forked pool) finish bit-identical to a swap-free twin, and the
    admission matches a fresh engine deployed directly on the new
    bank."""
    from repro.deploy import PlanCache

    cfg = _cfg(cim=True)
    params, params2 = _params(cfg), _params(cfg, seed=1)
    model = NonidealModel(drift_nu=0.05, sigma_program=0.02)
    old, new = (None, model) if to == "nonideal" else (model, None)
    prompts = _prompts(3, seed=17)

    with tempfile.TemporaryDirectory() as tmp:
        def engine(p, nonideal):
            return ContinuousEngine(cfg, p, capacity=3, max_seq=64,
                                    max_prompt=16,
                                    plan_cache=PlanCache(tmp),
                                    nonideal=nonideal)

        def fly(eng):
            rids = [eng.submit(prompts[i], max_tokens=8,
                               temperature=0.5 * i, seed=60 + i)
                    for i in range(2)]
            eng.step()                       # both in flight, epoch 0
            return rids

        ref = engine(params, old)
        ref_out = [ref.run()[r] for r in fly(ref)]

        eng = engine(params, old)
        fmt = eng.pool.state_format
        rids = fly(eng)
        eng.begin_redeploy(params2, nonideal=new).join()
        rid = eng.submit(prompts[2], max_tokens=6, temperature=0.7, seed=99)
        eng.step()                 # installs epoch 1, admits onto it
        assert len(eng.scheduler.epochs_live()) == 2
        out = eng.run()
        assert [out[r] for r in rids] == ref_out
        assert eng.traces["decode"] == 2     # one program per tree
        assert eng.pool.state_format is fmt
        assert eng.pool.traces["merge"] == 1

        fresh = engine(params2, new)
        rid_f = fresh.submit(prompts[2], max_tokens=6, temperature=0.7,
                             seed=99)
        assert out[rid] == fresh.run()[rid_f]


@pytest.mark.parametrize("window", [0, 16])
def test_in_place_decode_matches_full_forward_under_churn(window):
    """The slot-pool decode writes each token into its lane's ring in
    place and attends in one pass; its greedy tokens equal a cache-free
    full-sequence forward of each request, teacher-forced, under churn:
    joins into freed slots, dead lanes, a ring that wraps
    (``sliding_window`` 16 against ``max_seq`` 64: positions reach 31),
    and a multi-bank swap (requests pinned to two bank epochs, decoded
    on a forked pool and merged)."""
    from repro.deploy import PlanCache
    from repro.distributed.sharding import ShardingCtx
    from repro.models.model import apply_model

    cfg = _cfg(cim=True).replace(sliding_window=window)
    params, params2 = _params(cfg), _params(cfg, seed=1)
    rs = np.random.RandomState(21)
    lens, budgets = [3, 8, 5, 7, 4, 8, 6], [24, 5, 12, 9, 24, 3, 17]
    prompts = [rs.randint(0, VOCAB, size=n).astype(np.int32) for n in lens]
    with tempfile.TemporaryDirectory() as tmp:
        eng = ContinuousEngine(cfg, params, capacity=3, max_seq=64,
                               max_prompt=8, plan_cache=PlanCache(tmp))
        banks = {0: (params, eng.banks[0].cim)}
        first = [eng.submit(prompts[i], max_tokens=budgets[i])
                 for i in range(3)]
        eng.step()
        eng.begin_redeploy(params2).join()
        late = [eng.submit(prompts[i], max_tokens=budgets[i])
                for i in range(3, 7)]
        eng.step()                           # installs epoch 1
        banks[1] = (params2, eng.banks[eng.serving_epoch].cim)
        for _ in range(8):                   # a late request takes a
            if len(eng.scheduler.epochs_live()) == 2:   # freed slot
                break
            eng.step()
        assert len(eng.scheduler.epochs_live()) == 2
        out = eng.run()
    assert eng.traces["decode"] == 1 and eng.pool.traces["merge"] == 1

    @jax.jit
    def forward(params, cim, tokens):
        logits, _, _ = apply_model(params, cfg, ShardingCtx(),
                                   tokens=tokens[None], cim=cim)
        return jnp.argmax(logits[0], axis=-1)

    for rids, epoch in ((first, 0), (late, 1)):
        for rid in rids:
            prompt, toks = prompts[rid], out[rid]
            assert len(toks) == budgets[rid]
            seq = np.zeros(64, np.int32)
            seq[:len(prompt) + len(toks) - 1] = np.concatenate(
                [prompt, toks[:-1]])
            want = np.asarray(forward(*banks[epoch], jnp.asarray(seq)))
            L = len(prompt)
            assert list(want[L - 1:L - 1 + len(toks)]) == toks, rid


def test_engine_rejects_oversized_prompts_and_bad_configs():
    cfg = _cfg()
    params = _params(cfg)
    eng = ContinuousEngine(cfg, params, capacity=1, max_seq=32,
                           max_prompt=8)
    with pytest.raises(ValueError):
        eng.submit(np.arange(9, dtype=np.int32), max_tokens=1)
    with pytest.raises(ValueError):
        ContinuousEngine(cfg, params, capacity=1, max_seq=8,
                         max_prompt=16)

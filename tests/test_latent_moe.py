"""The latent-attention MoE LM (nn/latent_moe.py) served through the
paged path, against the plain float32 reference of the benchmark
(perfbench/references/latent_moe_lm.py), on seeded weights at a tiny
size:

- the program's prefill in one, two and three page passes and then its
  decode through the latent paged cache give the reference's full
  forward pass's logits (float32 throughout: the tolerance is float32
  rounding, the absorbed form and the grouped product summing in
  another order than the reference's naive form and per-expert loop);
- the grouped expert product against a loop over experts, with empty
  experts, every token on one expert, and a layer's experts taken from
  a stack;
- the router: the bias moves the choice and never the weights, which
  are the chosen scores normalised and scaled;
- the latent decode kernel (interpret mode) against its twin on the
  pool;
- the latent pool under the cache's allocator, prefix sharing and the
  copy-on-write fork, and the GPT model's two pools as they were;
- the model behind ``ModelHost``: greedy requests take the step queued
  ahead on the device's ids, the steps carry the experts' counts, and
  a request that asks for no logits keeps none.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from deeplearning4j_tpu.nn.latent_moe import DEFAULT_INIT, LatentMoELM
from deeplearning4j_tpu.nn.transformer import CausalTransformerLM, prefill_plan
from deeplearning4j_tpu.ops import latent_attention as la
from deeplearning4j_tpu.ops.experts import (expert_counts, grouped_experts,
                                            route)
from deeplearning4j_tpu.runtime import telemetry
from deeplearning4j_tpu.serving import (ManualClock, ModelHost,
                                        PagedSequenceScheduler)
from deeplearning4j_tpu.serving.kvcache import PagedKVCache
from perfbench import harness

from conftest import drop_jax_caches_fixture

_drop_jax_caches_after_module = drop_jax_caches_fixture()

REF = harness.load_module("references", "latent_moe_lm")

CONFIG = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=24, n_routed_experts=8,
    n_shared_experts=1, num_experts_per_tok=4, routed_scaling_factor=1.8,
    first_k_dense_replace=1, num_hidden_layers=3, rms_norm_eps=1e-5,
    rope_theta=1e6, vocab_size=101, n_group=1, topk_group=1,
    norm_topk_prob=True, tie_word_embeddings=False)
PAGE = 8
#: float32 program against float32 reference: the absorbed attention,
#: the grouped product and the paged passes sum in other orders than the
#: reference's naive form, so rows agree to float32 rounding of values
#: of order one (read: under 1e-6 at these sizes), not bitwise
TOL = 1e-5


@pytest.fixture(scope="module")
def model():
    return LatentMoELM(CONFIG, max_context=64, page_size=PAGE,
                       dtype="float32", seed=3)


@pytest.fixture(scope="module")
def weights():
    return REF.make_weights(3, dict(CONFIG, dtype="float32",
                                    init=DEFAULT_INIT))


def _ref_logits(weights, prompt, served):
    return np.asarray(REF.served_logits(
        weights, dict(CONFIG, dtype="float32"), prompt, served,
        pad_to=REF.QUERY_BLOCK))


def _generate(m, prompt, n_new):
    """Prefill by prefill_plan, then n_new - 1 decode steps on a
    bucket of two (the second slot padded): greedy tokens and rows."""
    pool = jnp.zeros((m.n_layers, 16) + m.page_shapes()[0], jnp.float32)
    bt = np.zeros((m.max_pages_per_slot,), np.int32)
    bt[:] = np.arange(1, 9)
    for t0, nv, C in prefill_plan(len(prompt), 0, PAGE, m.max_pages_per_slot):
        chunk = np.zeros((C,), np.int32)
        chunk[:nv] = prompt[t0:t0 + nv]
        last, pool = m._jit_prefill(m._params, chunk, jnp.asarray(t0),
                                    jnp.asarray(nv), pool, bt)
    rows = [np.asarray(last)]
    toks = [int(np.argmax(rows[-1]))]
    bts = np.zeros((2, m.max_pages_per_slot), np.int32)
    bts[0] = bt
    for j in range(n_new - 1):
        (ids, logits, counts), pool = m._jit_decode(
            m._params, np.array([toks[-1], 0], np.int32), pool, bts,
            np.array([len(prompt) + j, 0], np.int32),
            jnp.zeros((2,), jnp.int32), np.array([-1, -1], np.int32))
        rows.append(np.asarray(logits)[0])
        toks.append(int(ids[0]))
        assert counts.shape == (2, 8) and int(counts.sum()) == 2 * 4
    return toks, np.stack(rows)


class TestAgainstTheReference:
    def test_weights_are_the_rule_s(self, model, weights):
        """The program's tensors are the reference's draws, split for the
        absorbed form."""
        p = model._params
        assert np.array_equal(p["embed"], weights["embed"])
        assert np.array_equal(p["moe"]["experts_down"],
                              weights["experts_down"])
        kv = np.asarray(weights["kv_b"][1]).reshape(32, 4, 32)
        assert np.array_equal(p["moe"]["w_uk"][0],
                              np.transpose(kv[..., :16], (1, 2, 0)))
        assert float(np.std(weights["router_bias"])) > 0

    @pytest.mark.parametrize("prompt_len", [8, 13, 21, 40])
    def test_prefill_passes_then_decode_give_the_full_forward_pass(
            self, model, weights, prompt_len):
        """Prompts of one, two, three and five pages (3 + 2 passes), then
        seven decode steps through the latent cache."""
        prompt = (np.arange(prompt_len, dtype=np.int32) * 7 + 3) % 101
        toks, rows = _generate(model, prompt, 8)
        ref = _ref_logits(weights, prompt, toks)
        np.testing.assert_allclose(rows, ref, rtol=0, atol=TOL)
        assert toks == ref.argmax(axis=1).tolist()


def _expert_loop(x, idx, w, gate_up, down):
    f = down.shape[1]
    out = np.zeros((x.shape[0], down.shape[2]))
    for n in range(x.shape[0]):
        for j in range(idx.shape[1]):
            e = idx[n, j]
            gu = x[n] @ gate_up[e]
            h = gu[:f] / (1 + np.exp(-gu[:f])) * gu[f:]
            out[n] += w[n, j] * (h @ down[e])
    return out


class TestExperts:
    @pytest.mark.parametrize("case", ["spread", "empty_experts",
                                      "all_on_one", "from_a_stack"])
    def test_grouped_product_is_the_per_expert_loop(self, case):
        rng = np.random.default_rng(5)
        N, k, E, d, f = 9, 3, 6, 8, 5
        x = rng.standard_normal((N, d)).astype(np.float32)
        gate_up = rng.standard_normal((E, d, 2 * f)).astype(np.float32) / 3
        down = rng.standard_normal((E, f, d)).astype(np.float32) / 3
        w = rng.random((N, k)).astype(np.float32)
        idx = np.stack([rng.permutation(E)[:k] for _ in range(N)])
        if case == "empty_experts":
            idx = np.stack([rng.permutation(3)[:k] for _ in range(N)]) * 2
        if case == "all_on_one":
            idx = np.full((N, k), 4)
        first = 0
        gu, dn = gate_up, down
        if case == "from_a_stack":       # the layer's experts are 2nd of 3
            first = E
            gu = np.concatenate([gate_up * 0 + 9, gate_up, gate_up - 9])
            dn = np.concatenate([down + 9, down, down * 0])
        got = grouped_experts(jnp.asarray(x), jnp.asarray(idx, jnp.int32),
                              jnp.asarray(w), jnp.asarray(gu),
                              jnp.asarray(dn), first)
        np.testing.assert_allclose(np.asarray(got),
                                   _expert_loop(x, idx, w, gate_up, down),
                                   rtol=1e-5, atol=1e-5)

    def test_counts_take_live_tokens_only(self):
        idx = jnp.asarray([[0, 2], [2, 3], [1, 0]], jnp.int32)
        live = jnp.asarray([True, True, False])
        assert expert_counts(idx, live, 5).tolist() == [1, 0, 2, 1, 0]

    def test_bias_moves_the_choice_not_the_weights(self):
        """Scores sigmoid(logits); the bias lifts expert 3 past expert 1
        into the top two; the weights are the chosen *scores* over their
        sum, times the scaling."""
        x = jnp.eye(4, dtype=jnp.float32)[:1]            # picks row 0
        logits = np.array([[2.0, 1.0, -1.0, 0.5]], np.float32)
        w_r = jnp.asarray(np.concatenate([logits, np.zeros((3, 4),
                                                           np.float32)]))
        s = 1 / (1 + np.exp(-logits[0]))
        idx, w = route(x, w_r, jnp.zeros(4), 2, 1.8)
        assert idx.tolist() == [[0, 1]]
        np.testing.assert_allclose(w, [[1.8 * s[0] / (s[0] + s[1]),
                                        1.8 * s[1] / (s[0] + s[1])]],
                                   rtol=1e-6)
        idx, w = route(x, w_r, jnp.asarray([0.0, 0.0, 0.0, 0.2]), 2, 1.8)
        assert idx.tolist() == [[0, 3]]
        np.testing.assert_allclose(w, [[1.8 * s[0] / (s[0] + s[3]),
                                        1.8 * s[3] / (s[0] + s[3])]],
                                   rtol=1e-6)
        assert float(jnp.sum(w)) == pytest.approx(1.8)


class TestLatentKernel:
    @pytest.mark.parametrize("lengths", [
        (70, 190, 17), (70, 0, 17), (1, 128, 129),
        (256, 257, 17),     # a length on a group's edge, one row past it
        (272, 513, 48),     # last groups holding a single live page
        (640, 0, 300)])     # every entry of the table; a padded slot
    def test_kernel_is_its_twin_on_the_pool(self, lengths):
        """Interpret mode, tables of 40 pages of 16 rows, three groups of
        16 a slot: one to forty live pages, groups whose pages are live
        in part, a padded slot (exact zeros); the twin takes the kernel's
        group a step and agrees bit for bit."""
        rng = np.random.default_rng(0)
        L, P, D, page, H, rank, MP = 2, 121, 48, 16, 4, 32, 40
        pool = jnp.asarray(rng.standard_normal((L, P, D, page)),
                           jnp.bfloat16)
        q = jnp.asarray(rng.standard_normal((3, H, D)), jnp.bfloat16)
        bts = rng.permutation(np.arange(1, P)).reshape(3, MP).astype(np.int32)
        sls = np.asarray(lengths, np.int32)
        got = la.latent_flash_decode(q, pool, 1, bts, sls, rank, 0.2,
                                     interpret=True)
        want = la.latent_attention(q, pool, 1, bts, sls, None, rank, 0.2)
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(want, np.float32))
        for s in np.flatnonzero(sls == 0):
            assert not np.any(np.asarray(got[s], np.float32))

    def test_operands_stand_still_past_the_last_live_page(self):
        """The operand table: a live page where the slot holds one; past
        the slot's last live page, the page the operand read one grid
        step before, or in the slot's first group (where it held another
        slot's page) the slot's last live page."""
        page, group = 16, 4
        bts = np.arange(100, 100 + 3 * 10).reshape(3, 10).astype(np.int32)
        lens = np.asarray([5 * page + 3, 2 * page, 0], np.int32)
        table = np.asarray(la._operand_pages(jnp.asarray(bts),
                                             jnp.asarray(lens), page, group))
        assert table.shape == (3, 12)
        assert table[0].tolist() == [100, 101, 102, 103, 104, 105, 102,
                                     103, 104, 105, 102, 103]
        assert table[1].tolist() == [110, 111, 111, 111] * 3
        assert table[2].tolist() == [120] * 12

    def test_fits_rule(self):
        """The GLM cell's shapes fit with the group's scores counted;
        128 heads' scores over a group's 2,048 keys do not."""
        assert la.latent_kernel_fits(128, 20, 576, 512, 2)
        assert not la.latent_kernel_fits(128, 128, 576, 512, 2)
        assert not la.latent_kernel_fits(8, 20, 576, 512, 2)
        assert la.latent_attention_impl(128, 20, 576, 512,
                                        jnp.bfloat16) == "reference"


class TestPools:
    def test_latent_pool_shares_prefixes_and_forks(self, model):
        """The model's one pool [L, P, R, page]: a registered prompt's
        pages adopted whole, the tail page forked on the first append
        with every layer's rows copied."""
        c = PagedKVCache(n_layers=3, page_size=PAGE, num_pages=8,
                         page_shapes=model.page_shapes(), dtype=np.float32)
        assert len(c.pools) == 1
        assert c.pools[0].shape == (3, 8, 40, PAGE)
        assert c.page_bytes() == 3 * 40 * PAGE * 4
        pages = c.alloc(2)
        c.pools = (c.pools[0].at[:, pages[1]].set(1.5),)
        tokens = list(range(12))
        c.register_prefix(tokens, pages, last_logits=np.zeros(5))
        got, n, logits = c.match_prefix(tokens)
        assert got == pages and n == 12 and logits is not None
        new = c.ensure_private(pages[1])
        assert new != pages[1]
        assert np.all(np.asarray(c.pools[0][:, new]) == 1.5)

    def test_gpt_model_keeps_its_two_pools(self):
        m = CausalTransformerLM(vocab=23, d_model=16, n_heads=2,
                                n_layers=2, max_context=32, page_size=8)
        s = PagedSequenceScheduler(m, num_pages=6, slot_buckets=(2,),
                                   clock=ManualClock(), start_thread=False)
        k, v = s.cache.pools
        assert k.shape == v.shape == (2, 6, 8, 2, 8)
        tok, sls, bts, src = s._new_staging(2)
        (ids, logits), k2, v2 = m._jit_decode(m._params, tok, k, v, bts,
                                              sls, s._no_ids(2), src)
        assert ids.shape == (2,) and logits.shape == (2, 23)
        s.close()


@pytest.fixture
def ring():
    trace = telemetry.get_registry().trace
    trace.clear()
    yield trace
    trace.clear()


class TestServed:
    def test_host_serves_it_on_the_queued_ahead_path(self, model, weights,
                                                     ring):
        """Three greedy requests behind ModelHost on a bucket of four:
        every step after each batch's first is queued on the device's
        ids, each step carries its experts' counts, the tokens and rows
        are the reference's, and the request that asked for no logits
        keeps none."""
        host = ModelHost(clock=ManualClock())
        host.register_sequence("glm", model, slotBuckets=(4,), numPages=24)
        prompts = [(np.arange(n, dtype=np.int32) * 5 + n) % 101
                   for n in (9, 17, 12)]
        reqs = [host.generate("glm", p, max_new_tokens=6, wait=False,
                              logits=i != 2)
                for i, p in enumerate(prompts)]
        host.sequence_model("glm").scheduler.drain()
        for i, (p, r) in enumerate(zip(prompts, reqs)):
            toks = r.wait(1.0).tolist()
            ref = _ref_logits(weights, p, toks)
            assert toks == ref.argmax(axis=1).tolist()
            if i == 2:
                assert r.logits is None
            else:
                np.testing.assert_allclose(r.logits, ref, rtol=0, atol=TOL)
        steps = sorted((sp for sp in ring.spans()
                        if sp["name"] == "sequence.step"),
                       key=lambda sp: (sp["ts"], sp["id"]))
        assert sum(sp["args"]["ahead"] for sp in steps) >= len(steps) - 3
        for sp in steps:
            a = sp["args"]
            assert a["experts_total"] == 2 * 8
            assert 0 < a["experts_touched"] <= min(16, 2 * 4 * a["slots"])
            assert 1 <= a["expert_tokens_max"] <= a["slots"]
        host.close()

    def test_rows_stay_on_the_device_unless_asked(self, model, ring):
        """With no request asking for logits and every one greedy, a
        collect fetches ids and counts only and lands nothing."""
        s = PagedSequenceScheduler(model, num_pages=24, slot_buckets=(2,),
                                   clock=ManualClock(), start_thread=False)
        r = s.submit(np.arange(10, dtype=np.int32), max_new_tokens=5,
                     wait=False, logits=False)
        s.drain()
        assert r.wait(1.0).shape == (5,) and r.logits is None
        fetches = [sp["args"]["bytes"] for sp in ring.spans()
                   if sp["name"] == "sequence.fetch"]
        assert fetches and all(b == 2 * 4 + 2 * 8 * 4 for b in fetches)
        assert not [sp for sp in ring.spans()
                    if sp["name"] == "sequence.land"]
        s.close()


def test_a_few_rows_are_gathered_on_the_device(ring):
    """Two requests of three ask for logits on a bucket of 16: each
    collect fetches the ids and eight gathered rows, not the [16, V]
    block, and their rows are the bits the whole block gives when every
    request asks."""
    m = CausalTransformerLM(vocab=23, d_model=16, n_heads=2, n_layers=2,
                            max_context=32, page_size=8)
    prompts = [np.arange(n, dtype=np.int32) % 23 for n in (5, 9, 12)]

    def serve(flags):
        s = PagedSequenceScheduler(m, num_pages=40, slot_buckets=(16,),
                                   clock=ManualClock(), start_thread=False)
        s.warm()
        reqs = [s.submit(p, max_new_tokens=5, wait=False, logits=f)
                for p, f in zip(prompts, flags)]
        s.drain()
        s.close()
        return reqs

    few = serve([True, False, True])
    fetched = {sp["args"]["bytes"] for sp in ring.spans()
               if sp["name"] == "sequence.fetch"}
    assert fetched == {16 * 4 + 8 * 23 * 4}
    every = serve([True, True, True])
    assert few[1].logits is None
    for a, b in zip(few, every):
        assert a.result.tolist() == b.result.tolist()
    for i in (0, 2):
        assert np.array_equal(few[i].logits, every[i].logits)

#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, the public entry points, one model at full width:

  train     zoo ResNet50 (1000 classes, 224x224, bf16, NHWC), batch 128,
            seeded synthetic data: ComputationGraph.fit(x, y) for a few
            steps, fitDataSet(it, stepsPerSync=k) for two blocks, output()
  serve     the same network behind ModelHost.register +
            InferenceServer.start(port=0), HTTP :predict requests of mixed
            batch sizes; served rows must equal output() on the same rows
            and the serving window must compile nothing
  kernels   flash_attention forward and jax.grad through it, COMPILED, at
            (B4,H8,T512,D64), (B4,H8,T8192,D64), (B2,H4,T4096,D128), bf16,
            against the XLA references; each paged kernel, on the whole
            pool with a layer index, against paged_attend at two shapes
            (H16 Dh64 page 16; the benchmark's H16 Dh128 page 128)
  sequence  CausalTransformerLM (d_model 2048, 16 heads, 8 layers, vocab
            32768, context 2048, page 16, bf16: the paged kernels' shape
            rule admits it), then one of d_model 1024 and 4 layers (head
            size 64: the rule refuses it, paged_attend serves it), behind
            ModelHost.register_sequence + InferenceServer, HTTP :generate
            requests with shared prefixes; tokens must equal
            dense_serial_trajectory (greedy), zero steady-state compiles,
            donated pools dead and their successors alive
  multichip only when >= 4 devices: ParallelWrapper(net).fit(it) and
            SharedTrainingMaster(net).fit(it) at global batch 128 against
            the one-chip trajectory, four devices holding shards/replicas

Each phase is fatal. Step walls end in block_until_ready and are labelled
with the device: they are information, not a benchmark.

Without an accelerator it exits 2 before doing any work. `--rehearse-cpu`
runs every phase at a tiny size on the CPU (Pallas in interpret mode) so
the script cannot rot between chip runs; a rehearsal never prints ok=true.

Last line of stdout on the chip: {"ok": true, "device": {...}}.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import importlib.metadata
import json
import sys
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.data.dataset import DataSetIterator
from deeplearning4j_tpu.ndarray import DataType
from deeplearning4j_tpu.nn import Nesterovs
from deeplearning4j_tpu.nn.transformer import (PREFILL_CHUNK_PAGES,
                                               CausalTransformerLM,
                                               dense_serial_trajectory)
from deeplearning4j_tpu.ops import pallas_attention as pa
from deeplearning4j_tpu.ops.attention import (blockwise_attention,
                                              dot_product_attention)
from deeplearning4j_tpu.parallel import ParallelWrapper, SharedTrainingMaster
from deeplearning4j_tpu.runtime import aot, compile_cache
from deeplearning4j_tpu.serving import (InferenceServer, ModelHost,
                                        greedy_sampler, stream_rng)
from deeplearning4j_tpu.zoo import ResNet50

# full width on the chip / tiny on the CPU rehearsal
FULL = dict(
    classes=1000, image=224, batch=128, fit_steps=5, steps_per_sync=2,
    buckets=(4, 8), request_rows=(1, 3, 4, 6),
    attn=((4, 8, 512, 64), (4, 8, 8192, 64), (2, 4, 4096, 128)),
    attn_block=512,
    # heads and pages of the sequence leg's second model (the shape rule
    # refuses them for serving; the kernels compile there all the same),
    # then the benchmark's, which the rule admits
    paged=(dict(L=2, S=8, H=16, Dh=64, page=16, MP=128, P=256),
           dict(L=2, S=16, H=16, Dh=128, page=128, MP=16, P=160)),
    # two served models and what the dispatcher must pick for each on the
    # chip. Dh 128, 16 heads, page 16: a shape the paged kernels' rule
    # admits, so the served tokens and the dense oracle both go through
    # the kernels. Dh 64: a shape it refuses, served through paged_attend
    # on the TPU backend, the branch a model of that head size gets
    lm=(dict(vocab=32768, d_model=2048, n_heads=16, n_layers=8,
             max_context=2048, page_size=16, dtype="bfloat16"),
        dict(vocab=32768, d_model=1024, n_heads=16, n_layers=4,
             max_context=2048, page_size=16, dtype="bfloat16")),
    lm_attend=("pallas", "reference"),
    lm_pages=512, lm_prefix=40, lm_new=8,
    # loss after k steps on 4 chips against the one-chip run on the same
    # global batch, as a fraction of the STARTING loss: the dense psum
    # differs in partitioning and bf16 rounding points only; the int8
    # all-reduce quantizes gradients and normalizes BN per 32-row shard
    pw_loss_tol=0.05, stm_loss_tol=0.25,
)
TINY = dict(
    classes=8, image=32, batch=16, fit_steps=4, steps_per_sync=2,
    buckets=(4, 8), request_rows=(1, 3, 4, 6),
    attn=((1, 2, 64, 16),), attn_block=16,
    paged=(dict(L=2, S=4, H=2, Dh=16, page=8, MP=8, P=48),),
    lm=(dict(vocab=61, d_model=32, n_heads=2, n_layers=2,
             max_context=64, page_size=8, dtype="float32"),),
    lm_attend=("reference",),
    lm_pages=48, lm_prefix=11, lm_new=4,
    # 4 rows per shard at 1x1 spatial: bf16 trajectories scatter, the
    # rehearsal only checks that they fall
    pw_loss_tol=1.0, stm_loss_tol=1.0,
)

# bf16 kernels against an fp32 oracle — the repo's XLA attention on the
# same values upcast to fp32, matmuls at "highest" precision (in bf16
# those forms carry their softmax state in bf16 and are noisier than the
# kernel: 2.2e-2 between the two at T=8192 on the v5e) — as max |diff|
# over the oracle's largest magnitude. The kernel rounds its output to
# bf16 once (2^-9) and its p@v / ds@k matmuls see bf16 operands
ATTN_FWD_TOL = 1e-2
ATTN_GRAD_TOL = 2e-2
# served rows against output() on the same rows, padded to the same
# bucket, as |delta log p| over the classes. One device: the same
# program, bitwise on the v5e. Several devices: the served batch is split
# (other conv shapes per device) and 50 bf16 layers turn that into logit
# noise — measured on four v5e chips: mean 0.006 / max 0.035 at one row
# per device, mean 0.059 / max 0.32 at two. The bound is on each row's
# mean (one class's log p is as noisy as its logit is large), and the
# log says how far apart DIFFERENT rows' outputs are (the brightness
# levels keep most of them well beyond it): a row or version mix-up
# lands there
SERVE_MEAN_DLOGP = 0.12


def log(msg):
    print(msg, flush=True)


class Smoke:
    def __init__(self, cfg, rehearsal):
        self.cfg = cfg
        self.rehearsal = rehearsal
        self.dev = jax.devices()[0]
        self.label = f"{self.dev.platform}:{self.dev.device_kind}"
        self.results = {}

    # -- bookkeeping ------------------------------------------------------
    @contextlib.contextmanager
    def phase(self, name):
        """Run one fatal phase; print compile seconds, persistent-cache
        hits/misses, wall and the device's peak memory."""
        log(f"--- phase {name} ---")
        t0 = time.perf_counter()
        with compile_cache.PersistentCacheWatch() as w:
            try:
                yield
            except BaseException as e:
                log(f"PHASE {name} FAIL {type(e).__name__}: {e}")
                raise
        stats = self.dev.memory_stats() or {}
        rec = dict(wall_s=round(time.perf_counter() - t0, 1),
                   compile_s=round(w.compile_seconds, 1),
                   persistent_cache_hits=w.hits,
                   persistent_cache_misses=w.misses,
                   peak_bytes_in_use=stats.get("peak_bytes_in_use"))
        self.results[name] = rec
        log(f"PHASE {name} PASS " + json.dumps(rec))

    def on_device(self, tree, what):
        """Every array leaf lives on this run's platform (the chip)."""
        leaves = [l for l in jax.tree_util.tree_leaves(tree)
                  if isinstance(l, jax.Array)]
        assert leaves, f"{what}: no array leaves"
        for leaf in leaves:
            plats = {d.platform for d in leaf.devices()}
            assert plats == {self.dev.platform}, (
                f"{what}: a leaf lives on {plats}, expected "
                f"{self.dev.platform}")
        return len(leaves)

    # -- the model under test ---------------------------------------------
    def resnet(self):
        c = self.cfg
        return ResNet50(numClasses=c["classes"],
                        inputShape=(3, c["image"], c["image"]),
                        updater=Nesterovs(0.002, 0.9),
                        dataType=DataType.BFLOAT16,
                        dataFormat="NHWC", seed=123).init()

    def batch(self, rows, seed=0):
        """Seeded synthetic NHWC images in [0, 1) on a 1/128 grid (short
        JSON for the HTTP requests), eight brightness levels so that
        rows are told apart by more than noise, and one-hot labels."""
        c = self.cfg
        rng = np.random.default_rng(seed)
        level = 1 + np.arange(rows).reshape(-1, 1, 1, 1) % 8
        x = (rng.integers(0, 16, (rows, c["image"], c["image"], 3))
             * level / 128.0).astype(np.float32)
        y = np.eye(c["classes"], dtype=np.float32)[
            rng.integers(0, c["classes"], rows)]
        return x, y

    # -- phases -----------------------------------------------------------
    def train(self):
        c = self.cfg
        net = self.resnet()
        x, y = self.batch(c["batch"])
        losses, walls = [], []
        for _ in range(c["fit_steps"]):
            t0 = time.perf_counter()
            net.fit(x, y)
            jax.block_until_ready(net._params)
            walls.append(time.perf_counter() - t0)
            losses.append(net.score())
        log(f"fit(x, y) b{c['batch']} on {self.label}: first call "
            f"{walls[0]:.1f}s (compile included), then "
            + ", ".join(f"{w * 1e3:.1f}ms" for w in walls[1:])
            + " per step (host feed + loss fetch included)")
        log("losses: " + ", ".join(f"{l:.4f}" for l in losses))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], \
            f"loss did not fall on a repeated batch: {losses}"
        self.one_chip_losses = losses

        k = c["steps_per_sync"]
        xs, ys = np.tile(x, (2 * k, 1, 1, 1)), np.tile(y, (2 * k, 1))
        it0 = net.getIterationCount()
        t0 = time.perf_counter()
        net.fitDataSet(DataSetIterator(xs, ys, c["batch"]), stepsPerSync=k)
        jax.block_until_ready(net._params)
        wall = time.perf_counter() - t0
        assert net.getIterationCount() == it0 + 2 * k
        assert net._fit_dataset_syncs == 2, net._fit_dataset_syncs
        assert np.isfinite(net.score()) and net.score() < losses[0]
        log(f"fitDataSet(stepsPerSync={k}) two blocks on {self.label}: "
            f"{wall:.1f}s (compile included), loss {net.score():.4f}")

        rows = max(c["buckets"])
        out = net.output(x[:rows])
        probs = np.asarray(out.toNumpy(), np.float32)
        assert probs.shape == (rows, c["classes"])
        assert np.all(np.isfinite(probs))
        np.testing.assert_allclose(probs.sum(-1), 1.0, atol=2e-2)
        n = self.on_device(net._params, "params")
        n += self.on_device(net._upd_states, "updater state")
        n += self.on_device(net._states, "layer state")
        n += self.on_device(out.jax(), "output()")
        log(f"{n} parameter/updater/state/output leaves on "
            f"{self.dev.platform}")
        self.net, self.x = net, x

    def serve(self):
        c, net = self.cfg, self.net
        host = ModelHost()
        srv = None
        try:
            rep = host.register("resnet50", net,
                                batchBuckets=c["buckets"])
            log("register warm report: " + json.dumps(rep["warm"]))
            srv = InferenceServer(host).start(port=0)
            wait_ready(srv.port)
            offs = np.cumsum((0,) + c["request_rows"])
            reqs = [self.x[a:b] for a, b in zip(offs[:-1], offs[1:])]
            # output() on the same rows, zero-padded to the bucket the
            # server pads them to
            want = [np.asarray(net.output(aot.pad_batch(
                        r, aot.bucket_batch(len(r), c["buckets"])))
                        .toNumpy(), np.float32)[:len(r)] for r in reqs]
            lw = logp(np.concatenate(want))
            apart = np.abs(lw[:, None] - lw[None]).mean(-1)[
                np.triu_indices(len(lw), 1)]
            log(f"different rows' outputs differ by mean |dlogp| "
                f"{np.median(apart):.1e} (median over pairs), "
                f"{np.mean(apart > SERVE_MEAN_DLOGP):.0%} of pairs beyond "
                f"the per-row bound {SERVE_MEAN_DLOGP}")
            # one request per bucket first: nothing in the window below
            # is the first call of its kind
            for b in c["buckets"]:
                post(srv.port, "/v1/models/resnet50:predict",
                     {"instances": self.x[:b].tolist()})
            with aot.CompileWatch() as cw, \
                    compile_cache.PersistentCacheWatch() as jw:
                for r, ref in zip(reqs, want):
                    t0 = time.perf_counter()
                    body = post(srv.port, "/v1/models/resnet50:predict",
                                {"instances": r.tolist()})
                    wall = time.perf_counter() - t0
                    got = np.asarray(body["predictions"], np.float32)
                    assert got.shape == ref.shape, (got.shape, ref.shape)
                    d = np.abs(logp(got) - logp(ref))
                    log(f":predict rows={len(r)} on {self.label}: "
                        f"{wall * 1e3:.0f}ms (JSON + queue + dispatch); "
                        f"|dlogp| row mean <= {d.mean(-1).max():.1e}, max "
                        f"{d.max():.1e}, "
                        f"bitwise_equal_output={np.array_equal(got, ref)}")
                    assert d.mean(-1).max() <= SERVE_MEAN_DLOGP, d.mean(-1)
            cw.assert_no_compiles("ResNet-50 serving window")
            assert jw.hits + jw.misses == 0, (
                f"{jw.hits + jw.misses} XLA compile request(s) inside "
                "the serving window")
            log("served rows match output() on the same rows; 0 compiles "
                "in the serving window")
        finally:
            if srv is not None:
                srv.stop()
            host.close(drain=True)

    def kernels(self):
        c = self.cfg
        if self.rehearsal:
            pa._INTERPRET = True    # the CPU has no Mosaic; never on chip
        blk = c["attn_block"]
        for (B, H, T, D) in c["attn"]:
            if not self.rehearsal:
                fits = pa._kernel_fits(T, T, D, 2, blk, blk)
                assert pa._choose_impl(T, on_tpu=True,
                                       kernel_fits=fits) == "flash", \
                    f"dispatcher would not run the kernel at T={T} D={D}"
            rng = np.random.default_rng(T + D)
            q, k, v, g = (jnp.asarray(rng.standard_normal((B, H, T, D)),
                                      jnp.bfloat16) for _ in range(4))
            # attention is independent per (batch, head) and so is the
            # loss below: the oracle runs on a [:1, :2] slice, whose
            # scan residuals fit the chip at T=8192, and is compared
            # with the same slice of the kernel's results
            cut = (slice(0, 1), slice(0, 2))
            q32, k32, v32, g32 = (a[cut].astype(jnp.float32)
                                  for a in (q, k, v, g))
            # the fused reference materialises [B,H,T,T] scores: only
            # where that is small; the scan elsewhere
            ref_fn = dot_product_attention if T <= 1024 else \
                functools.partial(blockwise_attention, block_size=blk)
            for causal in (False, True):
                def loss(fn, q, k, v, g):
                    o = fn(q, k, v, causal=causal)
                    return jnp.sum(o.astype(jnp.float32)
                                   * g.astype(jnp.float32)), o

                flash = functools.partial(pa.flash_attention,
                                          block_q=blk, block_k=blk)
                run = jax.jit(jax.value_and_grad(
                    functools.partial(loss, flash), argnums=(0, 1, 2),
                    has_aux=True))
                ref = jax.jit(jax.value_and_grad(
                    functools.partial(loss, ref_fn), argnums=(0, 1, 2),
                    has_aux=True))
                (_, o), grads = run(q, k, v, g)
                with jax.default_matmul_precision("highest"):
                    (_, o_ref), grads_ref = ref(q32, k32, v32, g32)
                jax.block_until_ready(grads)
                t0 = time.perf_counter()
                jax.block_until_ready(run(q, k, v, g))
                wall = time.perf_counter() - t0
                e_fwd = rel_err(o[cut], o_ref)
                e_bwd = max(rel_err(a[cut], b)
                            for a, b in zip(grads, grads_ref))
                log(f"flash_attention fwd+bwd B{B} H{H} T{T} D{D} "
                    f"causal={causal} on {self.label}: {wall * 1e3:.1f}ms"
                    f"; fwd err {e_fwd:.1e} (tol {ATTN_FWD_TOL}), grad "
                    f"err {e_bwd:.1e} (tol {ATTN_GRAD_TOL})")
                assert e_fwd <= ATTN_FWD_TOL and e_bwd <= ATTN_GRAD_TOL

        # the paged kernels, once each, on the whole pool with a layer
        # index, against the serving path's portable form on that layer
        for p in c["paged"]:
            self.paged_kernels(**p)
        if self.rehearsal:
            pa._INTERPRET = False

    def paged_kernels(self, L, S, H, Dh, page, MP, P):
        dt = jnp.float32 if self.rehearsal else jnp.bfloat16
        li = L - 1
        rng = np.random.default_rng(7)
        kp, vp = (jnp.asarray(rng.standard_normal((L, P, page, H, Dh)), dt)
                  for _ in range(2))
        q = jnp.asarray(rng.standard_normal((S, H, Dh)), dt)
        lens = rng.integers(1, MP * page, S).astype(np.int32)
        lens[-1] = 0                                   # one padded slot
        bts = np.zeros((S, MP), np.int32)
        free = rng.permutation(np.arange(1, P))
        for s in range(S):
            n = -(-int(lens[s]) // page)
            n = min(n, (P - 1) // S)
            lens[s] = min(lens[s], n * page)
            bts[s, :n] = free[s * ((P - 1) // S):][:n]
        got = jax.jit(functools.partial(
            pa.paged_flash_decode, interpret=self.rehearsal))(
            q, kp, vp, bts, lens, layer=jnp.asarray(li, jnp.int32))
        want = pa.paged_attend(q[:, None], kp[li][bts], vp[li][bts],
                               jnp.asarray(lens), jnp.asarray(lens) - 1)[:, 0]
        e_dec = rel_err(got, want)
        assert np.all(np.asarray(got[-1], np.float32) == 0)
        # slot 0's last pages as one chunk: of one page, and of as many
        # as the scheduler's longest pass takes
        live0 = -(-int(lens[0]) // page)
        e_pre = 0.0
        for n in sorted({1, min(PREFILL_CHUNK_PAGES[-1], live0)}):
            t0 = page * (live0 - n)
            n_valid = int(lens[0]) - t0
            qc = jnp.asarray(rng.standard_normal((n * page, H, Dh)), dt)
            got = jax.jit(functools.partial(
                pa.paged_flash_prefill, interpret=self.rehearsal))(
                qc, kp, vp, bts[0], t0, n_valid,
                layer=jnp.asarray(li, jnp.int32))
            want = pa.paged_attend(
                qc[None], kp[li][bts[0]][None], vp[li][bts[0]][None],
                jnp.asarray([t0 + n_valid]), jnp.asarray([t0]))[0]
            e_pre = max(e_pre, rel_err(got[:n_valid], want[:n_valid]))
        rule = pa._paged_kernel_fits(page, H, Dh, jnp.dtype(dt).itemsize)
        log(f"paged_flash_decode S{S} H{H} Dh{Dh} page{page} MP{MP} "
            f"layer {li} of {L} (shape rule: {rule}) vs paged_attend: err "
            f"{e_dec:.1e}; paged_flash_prefill, chunks of 1 and {n} "
            f"pages: err {e_pre:.1e} (tol {ATTN_FWD_TOL})")
        assert e_dec <= ATTN_FWD_TOL and e_pre <= ATTN_FWD_TOL

    def sequence(self):
        for lm, attend in zip(self.cfg["lm"], self.cfg["lm_attend"]):
            self.serve_lm(lm, attend)

    def serve_lm(self, lm, attend):
        c = self.cfg
        model = CausalTransformerLM(seed=3, **lm)
        log(f"sequence model d_model {lm['d_model']} attends through "
            f"{model.attend_impl()!r}")
        assert model.attend_impl() == attend
        self.on_device(model._params, "LM params")
        rng = np.random.default_rng(11)
        prefix = rng.integers(0, model.vocab, c["lm_prefix"]).tolist()
        tails = [rng.integers(0, model.vocab, n).tolist()
                 for n in (5, 9, 2)]
        prompts = [prefix + t for t in tails]
        prompts.append(list(prompts[0]))     # exact repeat: whole prefix
        bucket = 4
        host = ModelHost()
        srv = None
        try:
            rep = host.register_sequence("lm", model, slotBuckets=(bucket,),
                                         numPages=c["lm_pages"])
            log("register_sequence warm report: " + json.dumps(rep["warm"]))
            sched = host.sequence_model("lm").scheduler
            old_k = sched.cache.k_pools
            srv = InferenceServer(host).start(port=0)
            wait_ready(srv.port)
            got = []
            with aot.CompileWatch() as cw, \
                    compile_cache.PersistentCacheWatch() as jw:
                for p in prompts:
                    t0 = time.perf_counter()
                    body = post(srv.port, "/v1/models/lm:generate",
                                {"tokens": p, "maxNewTokens": c["lm_new"]})
                    wall = time.perf_counter() - t0
                    got.append(body["tokens"])
                    log(f":generate prompt={len(p)} new={c['lm_new']} on "
                        f"{self.label}: {wall * 1e3:.0f}ms, "
                        f"tokens {body['tokens']}")
            cw.assert_no_compiles("sequence serving window")
            log(f"0 step-function compiles in the window (JAX saw "
                f"{jw.hits + jw.misses} compile request(s) for host-side "
                "eager ops)")
            assert old_k.is_deleted(), \
                "the KV pool handed to the first step was not donated"
            pools = (sched.cache.k_pools, sched.cache.v_pools)
            assert not any(a.is_deleted() for a in pools)
            self.on_device(pools, "KV pools")
            assert all(np.isfinite(np.asarray(a, np.float32)).all()
                       for a in pools)
            log("donated pools are dead, their successors alive and "
                f"finite on {self.dev.platform}")
        finally:
            if srv is not None:
                srv.stop()
            host.close(drain=True)
        for i, (p, toks) in enumerate(zip(prompts, got)):
            want, _ = dense_serial_trajectory(
                model, p, c["lm_new"], greedy_sampler(), stream_rng(0, i),
                bucket=bucket)
            assert toks == want, (
                f"request {i}: served {toks} != dense serial {want}")
        log(f"{len(prompts)} generations equal dense_serial_trajectory")

    def multichip(self):
        c = self.cfg
        devs = jax.devices()
        steps = c["fit_steps"]
        x, y = self.batch(c["batch"])
        xs, ys = np.tile(x, (steps, 1, 1, 1)), np.tile(y, (steps, 1))
        ref = self.one_chip_losses
        for cls, tol in ((ParallelWrapper, c["pw_loss_tol"]),
                         (SharedTrainingMaster, c["stm_loss_tol"])):
            net = self.resnet()
            losses = []
            net.setListeners(ScoreTap(losses))
            trainer = cls(net)
            t0 = time.perf_counter()
            trainer.fit(DataSetIterator(xs, ys, c["batch"]))
            jax.block_until_ready(net._params)
            wall = time.perf_counter() - t0
            log(f"{cls.__name__}(net).fit(it) b{c['batch']} x{steps} on "
                f"{len(devs)}x {self.label}: {wall:.1f}s (compile "
                "included); losses "
                + ", ".join(f"{l:.4f}" for l in losses))
            assert len(losses) == steps and all(np.isfinite(losses))
            assert losses[-1] < losses[0], losses
            assert abs(losses[-1] - ref[-1]) <= tol * ref[0], (
                f"{cls.__name__} loss {losses[-1]} vs one chip {ref[-1]} "
                f"after {steps} steps: gap over {tol} of the starting "
                f"loss {ref[0]}")
            for leaf in jax.tree_util.tree_leaves(net._params):
                assert leaf.sharding.device_set == set(devs), (
                    "a parameter is not replicated over every device: "
                    f"{leaf.sharding}")
            shard_devs = {s.device for s in
                          trainer._shard_batch(x).addressable_shards}
            assert shard_devs == set(devs), shard_devs
        log(f"{len(devs)} devices hold batch shards and parameter "
            f"replicas; loss after {steps} steps within "
            f"{c['pw_loss_tol']} (dense psum) / {c['stm_loss_tol']} (int8 "
            f"all-reduce) of the starting loss from the one-chip run "
            f"({ref[-1]:.4f})")


class ScoreTap:
    """TrainingListener that records the loss of every iteration."""

    def __init__(self, sink):
        self.sink = sink

    def iterationDone(self, model, iteration, epoch):
        self.sink.append(model.score())


def logp(p):
    """log of softmax outputs, floored at 1e-6: below that a class does
    not matter to any prediction, and bf16 underflows to exact zeros."""
    return np.log(np.maximum(p, 1e-6))


def rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def post(port, path, body, timeout=300):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        raise AssertionError(
            f"POST {path} -> {e.code}: {e.read()[:500]!r}") from None


def wait_ready(port, timeout=600):
    deadline = time.monotonic() + timeout
    while True:
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
                if r.status == 200:
                    return
        except urllib.error.HTTPError as e:
            if e.code != 503:
                raise
            err = json.loads(e.read() or b"{}").get("warmupError")
            assert not err, f"server warm-up failed: {err}"
        assert time.monotonic() < deadline, "server never became ready"
        time.sleep(0.2)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run every phase at a tiny size on the CPU; "
                         "prints the platform and never ok=true")
    args = ap.parse_args(argv)

    devs = jax.devices()
    platform = devs[0].platform
    if args.rehearse_cpu:
        if platform != "cpu":
            sys.exit(f"--rehearse-cpu is for the CPU; JAX started on "
                     f"{platform!r}. Run without it on the chip.")
    elif platform != "tpu":
        print(f"chip_smoke: no accelerator (platform={platform!r}); "
              "nothing was run. Use the chip tool, or --rehearse-cpu for "
              "a tiny CPU rehearsal.", file=sys.stderr)
        sys.exit(2)

    def version(pkg):
        try:
            return importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            return "not installed"

    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"platform={platform} device_kind={devs[0].device_kind!r} "
        f"count={len(devs)} jax={jax.__version__} "
        f"jaxlib={version('jaxlib')} libtpu={version('libtpu')} "
        f"x64={bool(jax.config.jax_enable_x64)}")
    log(f"compile cache: {compile_cache.configure()}")
    if args.rehearse_cpu:
        log("REHEARSAL on the CPU at a tiny size: not a chip result")

    smoke = Smoke(TINY if args.rehearse_cpu else FULL, args.rehearse_cpu)
    phases = [smoke.train, smoke.serve, smoke.kernels, smoke.sequence]
    if len(devs) >= 4:
        phases.append(smoke.multichip)
    t0 = time.perf_counter()
    for fn in phases:
        with smoke.phase(fn.__name__):
            fn()
    log(f"all {len(phases)} phases passed in "
        f"{time.perf_counter() - t0:.0f}s: " + json.dumps(smoke.results))
    print(json.dumps({"ok": not args.rehearse_cpu, "device": device,
                      **({"rehearsal": True} if args.rehearse_cpu
                         else {})}), flush=True)


if __name__ == "__main__":
    main()

"""Every pallas_call in ops/pallas_attention.py must pass the TPU lowering
— and, where libtpu is installed, the Mosaic compiler — from the CPU.

Interpret mode checks values; it does not check block shapes, layouts or
VMEM. PRs 12 and 19 shipped kernels that were bitwise-correct in
interpret mode and refused by the TPU lowering ("the last two dimensions
of your block shape [must be] divisible by 8 and 128 ... or be equal to
the respective dimensions of the overall array"); nothing in tier-1 could
see it. Two no-chip recipes close that gap, at the shapes chip_smoke.py
runs on the chip:

* ``jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))`` runs the
  Pallas->Mosaic lowering (block-shape rules) on any machine;
* ``jax.experimental.topologies.get_topology_desc("v5e:2x2", "tpu")``
  + ``.lower().compile()`` runs the real TPU compiler (layout inference,
  scoped VMEM) when libtpu is present.

x64 is switched off around both: the chip runs with the default, and the
suite's fp64 mode would lower 64-bit index arithmetic Mosaic never sees.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.nn.transformer import PREFILL_CHUNK_PAGES
from deeplearning4j_tpu.ops import pallas_attention as pa

# chip_smoke.py's kernel-phase shapes (B, H, T, D), bf16
ATTN_SHAPES = [(4, 8, 512, 64), (4, 8, 8192, 64), (2, 4, 4096, 128)]
# chip_smoke.py's paged shape: the sequence-serving model's heads/pages
PAGED = dict(S=8, H=16, Dh=64, page=16, MP=128, P=256)
# the benchmark's own (perfbench/configs/cerebras-gpt-1.3b.json): the
# whole pools with a layer index, as the step functions pass them
PAGED_BENCH = dict(L=24, S=16, H=16, Dh=128, page=128, MP=16, P=320)


@pytest.fixture(scope="module")
def v5e():
    """A compile-only v5e device; skips where libtpu cannot describe one
    (the lowering half still runs there)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu: the lowering half still runs
        pytest.skip(f"no TPU topology descriptor here: {e}")
    return topo.devices[0]


def _sds(shape, dtype, device=None):
    sharding = None if device is None else \
        jax.sharding.SingleDeviceSharding(device)
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _attn_cases(device=None):
    for B, H, T, D in ATTN_SHAPES:
        q = _sds((B, H, T, D), jnp.bfloat16, device)
        for causal in (False, True):
            fwd = functools.partial(pa._flash, causal=causal, block_q=512,
                                    block_k=512)
            yield f"fwd T{T} D{D} causal={causal}", fwd, (q, q, q)

            def loss(q, k, v, causal=causal):
                return jnp.sum(pa._flash(q, k, v, causal, 512, 512)
                               .astype(jnp.float32))

            # forward-with-lse + the dq and dk/dv kernels
            yield (f"grad T{T} D{D} causal={causal}",
                   jax.grad(loss, argnums=(0, 1, 2)), (q, q, q))


def _paged_cases(device=None, dtype=jnp.bfloat16):
    for p in (PAGED, PAGED_BENCH):
        whole = "L" in p
        tag = "whole pool, benchmark shape" if whole else "a layer's pool"
        shape = (p["P"], p["page"], p["H"], p["Dh"])
        pool = _sds(((p["L"],) if whole else ()) + shape, dtype, device)
        layer = (_sds((), jnp.int32, device),) if whole else ()
        q = _sds((p["S"], p["H"], p["Dh"]), dtype, device)
        bts = _sds((p["S"], p["MP"]), jnp.int32, device)
        lens = _sds((p["S"],), jnp.int32, device)
        for need_lse in (False, True):
            def decode(q, kp, vp, bts, lens, layer=None,
                       need_lse=need_lse):
                return pa.paged_flash_decode(q, kp, vp, bts, lens,
                                             layer=layer, need_lse=need_lse,
                                             interpret=False)

            yield (f"paged decode lse={need_lse}, {tag}", decode,
                   (q, pool, pool, bts, lens) + layer)

        def prefill(qc, kp, vp, bt, t0, n_valid, layer=None):
            return pa.paged_flash_prefill(qc, kp, vp, bt, t0, n_valid,
                                          layer=layer, interpret=False)

        for n in PREFILL_CHUNK_PAGES:     # every chunk length kept
            yield (f"paged prefill of {n} pages, {tag}", prefill,
                   (_sds((n * p["page"], p["H"], p["Dh"]), dtype, device),
                    pool, pool, _sds((p["MP"],), jnp.int32, device),
                    _sds((), jnp.int32, device),
                    _sds((), jnp.int32, device)) + layer)


def _all_cases(device=None):
    yield from _attn_cases(device)
    yield from _paged_cases(device)


def test_every_pallas_call_passes_the_tpu_lowering():
    with jax.enable_x64(False):
        for name, fn, args in _all_cases():
            try:
                jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
            except Exception as e:
                pytest.fail(f"{name}: refused by the TPU lowering: {e}")


def test_every_pallas_call_compiles_for_v5e(v5e):
    with jax.enable_x64(False):
        for name, fn, args in _all_cases(v5e):
            try:
                jax.jit(fn).lower(*args).compile()
            except Exception as e:
                pytest.fail(f"{name}: refused by the TPU compiler: "
                            f"{str(e)[:1500]}")


@pytest.mark.parametrize("D,dtype", [(64, jnp.bfloat16), (128, jnp.bfloat16),
                                     (64, jnp.float32), (128, jnp.float32)])
def test_dispatch_rule_admits_only_shapes_that_compile(v5e, D, dtype):
    """The largest T _kernel_fits admits must compile (forward-with-lse
    and both backward kernels); the kernel's own limit lies beyond it,
    so the rule is the conservative side of a measured boundary."""
    itemsize = jnp.dtype(dtype).itemsize
    T = max(t for t in range(512, 65536, 512)
            if pa._kernel_fits(t, t, D, itemsize, 512, 512))
    assert not pa._kernel_fits(T + 512, T + 512, D, itemsize, 512, 512)
    q = _sds((2, 4, T, D), dtype, v5e)

    def loss(q, k, v):
        return jnp.sum(pa._flash(q, k, v, True, 512, 512)
                       .astype(jnp.float32))

    with jax.enable_x64(False):
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).compile()


def test_dispatch_rule_rejects_unaligned_blocks():
    # blocks that tile the sequence must be multiples of 128
    assert pa._kernel_fits(8192, 8192, 64, 2, 512, 512)
    assert not pa._kernel_fits(8192, 8192, 64, 2, 512, 100)
    assert not pa._kernel_fits(70, 8192, 64, 2, 512, 512)   # bq = Tq = 70
    # ...and the dispatcher then streams instead of raising
    assert pa._choose_impl(8192, on_tpu=True, kernel_fits=False) \
        == "blockwise"
    assert pa._choose_impl(8192, on_tpu=True, kernel_fits=True) == "flash"


@pytest.mark.parametrize(
    "step", ["decode"] + [f"prefill-{n}-pages" for n in PREFILL_CHUNK_PAGES])
def test_the_benchmarks_step_functions_compile_with_the_kernels(
        v5e, monkeypatch, step):
    """The whole `_decode_paged`, and `_prefill_paged` at every chunk
    length the scheduler warms, of the benchmark's configuration, from
    shapes, for the described chip, the dispatcher steered to the
    kernels from here (this process sees a CPU): 24 custom calls each,
    and temporaries far under a pool's 4 GB — the page updates before
    each layer's kernel write the donated pool in place and the
    kernel's pool operand is no copy."""
    from deeplearning4j_tpu.nn.transformer import CausalTransformerLM

    p = PAGED_BENCH
    d, f, vocab, ctx = p["H"] * p["Dh"], 8192, 50257, p["MP"] * p["page"]
    dt = jnp.bfloat16

    def sd(*shape, dtype=dt):
        return _sds(shape, dtype, v5e)

    def shapes(self):
        layer = {"ln1": sd(d), "wq": sd(d, d), "wk": sd(d, d),
                 "wv": sd(d, d), "wo": sd(d, d), "ln2": sd(d),
                 "w1": sd(d, f), "w2": sd(f, d)}
        return {"emb": sd(vocab, d), "pos": sd(ctx, d), "lnf": sd(d),
                "layers": [dict(layer) for _ in range(p["L"])]}

    monkeypatch.setattr(CausalTransformerLM, "_init_params", shapes)
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    model = CausalTransformerLM(
        vocab=vocab, d_model=d, n_heads=p["H"], n_layers=p["L"], d_ff=f,
        max_context=ctx, page_size=p["page"], dtype="bfloat16")
    assert model.attend_impl() == "pallas"
    pool = sd(p["L"], p["P"], p["page"], p["H"], p["Dh"])
    i32 = jnp.int32
    if step == "decode":
        fn, donate, args = model._decode_paged, (2, 3), (
            model._params, sd(p["S"], dtype=i32), pool, pool,
            sd(p["S"], p["MP"], dtype=i32), sd(p["S"], dtype=i32),
            sd(p["S"], dtype=i32), sd(p["S"], dtype=i32))
    else:
        pages = int(step.split("-")[1])
        fn, donate, args = model._prefill_paged, (4, 5), (
            model._params, sd(pages * p["page"], dtype=i32), sd(dtype=i32),
            sd(dtype=i32), pool, pool, sd(p["MP"], dtype=i32))
    with jax.enable_x64(False):
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= p["L"], \
        f"{step}: the kernels are not in the compiled step"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 ** 30, \
        f"{step}: {temp / 1e9:.2f} GB of temporaries (a pool copy?)"

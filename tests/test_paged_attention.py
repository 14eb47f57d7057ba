"""Block-table paged-attention kernel gates (ops/pallas_attention.py
``paged_flash_decode`` / ``paged_flash_prefill`` / ``paged_attend``,
docs/SERVING.md "Paged KV cache").

What must hold (the ISSUE 19 kernel acceptance, as ISSUE 27 left it):

- the chunked-PREFILL kernel (page-sized prompt chunk attending
  causally over the table so far) is BITWISE the dense flash kernel's
  rows for every chunk — aligned, padded and bf16 grids, with the pool
  pages physically scattered; a chunk of several pages (ISSUE 31: one
  grid step per query tile of one page and table page) is BITWISE, row
  for row, the same rows fed as page-sized chunks;
- the paged DECODE kernel (one query row per slot, K/V read through
  the slot's block table) equals the dense flash kernel on the same
  tokens to a rounding of the output's dtype. It was bitwise until
  ISSUE 27, whose point 3 has it score all heads of a page in one
  product with operands in the pool's dtype (scale after the product,
  probabilities rounded to the pool's dtype for p.V), so its float32
  sums associate differently. The gap measured over 20 seeds of these
  grids, relative to max(|want|, 1/4): 5.8 float32 ulp, 1.0 bfloat16
  ulp; the limit here is 8 and 2;
- padded slots behave like the dense kernel's fully-masked rows: zero
  output, the +1e30 lse sentinel; pages past a slot's last live page
  are never read (a NaN there reaches no output) and widening the
  table changes no bit;
- the whole pool with a layer index is bitwise the layer's slice;
- the portable ``paged_attend`` core (the serving step functions'
  attention off the TPU) accumulates in the same page order: bitwise
  the prefill kernel in bf16 and <= 1 ulp from it in f32, the decode
  kernel's rounding from the decode kernel;
- ``paged_attention`` picks the kernels from the backend and the shape
  rule alone, and a ``CausalTransformerLM`` served through them gives
  the tokens of the ``paged_attend`` path.

Everything runs in pallas interpret mode on CPU — the same numerics
contract the dense flash kernel's parity suite uses.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import pallas_attention as pa


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setattr(pa, "_INTERPRET", True)


# interpret-mode pallas churns many tiny single-use executables; the
# shared hygiene fixture drops jax's global caches at module teardown
from conftest import drop_jax_caches_fixture

_drop_jax_caches_after_module = drop_jax_caches_fixture()


# ----------------------------------------------------------------------
# subjects
# ----------------------------------------------------------------------

def _paged_layout(T, page, P, H, D, dtype, rng, start_page=1):
    """Contiguous K/V [1, H, T, D] plus the SAME tokens scattered into
    a paged pool through a randomly permuted block table (physical
    page order deliberately != logical order)."""
    k = rng.standard_normal((1, H, T, D)).astype(np.float32)
    v = rng.standard_normal((1, H, T, D)).astype(np.float32)
    MP = -(-T // page)
    kp = np.zeros((P, page, H, D), np.float32)
    vp = np.zeros((P, page, H, D), np.float32)
    bt = np.zeros((MP,), np.int32)
    order = rng.permutation(np.arange(start_page, P))[:MP]
    for j in range(MP):
        pid = int(order[j])
        bt[j] = pid
        n = min(page, T - j * page)
        kp[pid, :n] = np.moveaxis(k[0, :, j * page:j * page + n], 0, 1)
        vp[pid, :n] = np.moveaxis(v[0, :, j * page:j * page + n], 0, 1)
    return (k.astype(dtype), v.astype(dtype), kp.astype(dtype),
            vp.astype(dtype), bt)


def _assert_within_rounding(got, want, dtype, what="", wider=1):
    """The decode kernel's distance from its per-head twins: a few
    roundings of `dtype` (8 float32 ulp, 2 bfloat16 ulp; `wider` times
    that where the sums are long), relative to the larger of |want|
    and 1/4."""
    eps = 2.0 ** -7 * 2 if dtype == jnp.bfloat16 else 2.0 ** -23 * 8
    eps *= wider
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = np.abs(got - want) / np.maximum(np.abs(want), 0.25)
    assert err.max() <= eps, f"{what}: {err.max():.3e} over {eps:.1e}"


def _bits(a):
    return np.asarray(a).view(np.uint8)


GRIDS = [
    pytest.param(8, 4, np.float32, id="aligned-f32"),
    pytest.param(7, 4, np.float32, id="padded-f32"),
    pytest.param(8, 4, jnp.bfloat16, id="aligned-bf16"),
    pytest.param(7, 4, jnp.bfloat16, id="padded-bf16"),
]


# ----------------------------------------------------------------------
# decode kernel vs the dense flash kernel
# ----------------------------------------------------------------------

class TestPagedDecodeParity:
    @pytest.mark.parametrize("T,page,dtype", GRIDS)
    def test_decode_within_rounding_of_dense_flash(self, T, page, dtype):
        """The block-table decode kernel's output for the last token is
        the dense flash kernel's last row (block_q=1, block_k=page —
        the same page order per head) to a rounding of the dtype: all
        heads of a page in one product (module docstring has the gap
        that was measured), pool pages scattered."""
        rng = np.random.default_rng(0)
        H, D, P = 2, 8, 12
        k, v, kp, vp, bt = _paged_layout(T, page, P, H, D, dtype, rng)
        q_full = rng.standard_normal((1, H, T, D)).astype(
            np.float32).astype(dtype)
        dense, _ = pa._flash_fwd_impl(jnp.asarray(q_full),
                                      jnp.asarray(k), jnp.asarray(v),
                                      True, 1, page, need_lse=False)
        dense_last = np.asarray(dense)[0, :, T - 1, :]
        S, MP = 2, bt.shape[0]
        bts = np.zeros((S, MP), np.int32)
        bts[0] = bt
        sls = np.zeros((S,), np.int32)
        sls[0] = T
        q = np.zeros((S, H, D), dtype)
        q[0] = np.moveaxis(q_full[0, :, T - 1], 0, 0)
        out = pa.paged_flash_decode(jnp.asarray(q), jnp.asarray(kp),
                                    jnp.asarray(vp), bts, sls)
        _assert_within_rounding(np.asarray(out)[0], dense_last, dtype,
                                "decode vs dense flash")

    @pytest.mark.parametrize("T,page,dtype", GRIDS)
    def test_padded_slot_rows_masked_like_dense(self, T, page, dtype):
        """A padded slot (seq_len 0, block table all null page) is the
        dense kernel's fully-masked row: zero output, +1e30 lse
        sentinel — never NaN, never garbage."""
        rng = np.random.default_rng(0)
        H, D, P = 2, 8, 12
        _, _, kp, vp, bt = _paged_layout(T, page, P, H, D, dtype, rng)
        S, MP = 2, bt.shape[0]
        bts = np.zeros((S, MP), np.int32)
        bts[0] = bt
        sls = np.zeros((S,), np.int32)
        sls[0] = T
        q = rng.standard_normal((S, H, D)).astype(np.float32).astype(dtype)
        out, lse = pa.paged_flash_decode(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), bts, sls,
            need_lse=True)
        assert np.all(np.asarray(out)[1] == 0)
        assert np.all(np.asarray(lse)[1] == pa._LSE_EMPTY)

    def test_trailing_null_pages_are_noops(self):
        """Blocks past a slot's live length run against the null page
        but contribute nothing: extending the block-table width leaves
        the output bitwise identical (the masked-block no-op the
        bounded-pool layout depends on)."""
        rng = np.random.default_rng(2)
        T, page, H, D, P = 12, 4, 2, 8, 16
        _, _, kp, vp, bt = _paged_layout(T, page, P, H, D,
                                         np.float32, rng)
        # poison the null page: a real no-op must mask it, not rely on
        # it being zero
        kp[0] = 7.5
        vp[0] = -3.25
        q = rng.standard_normal((1, H, D)).astype(np.float32)
        sls = np.asarray([T], np.int32)
        out_tight = pa.paged_flash_decode(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            bt[None], sls)
        wide = np.zeros((1, bt.shape[0] + 3), np.int32)
        wide[0, :bt.shape[0]] = bt
        out_wide = pa.paged_flash_decode(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            wide, sls)
        assert np.array_equal(np.asarray(out_tight).view(np.uint8),
                              np.asarray(out_wide).view(np.uint8))


# ----------------------------------------------------------------------
# chunked-prefill kernel vs the dense flash kernel
# ----------------------------------------------------------------------

class TestPagedPrefillParity:
    @pytest.mark.parametrize("T,page,dtype", GRIDS)
    def test_prefill_chunks_bitwise_vs_dense_flash(self, T, page, dtype):
        """Every page-sized prompt chunk's attention rows are BITWISE
        the dense flash kernel's rows over the same prefix (block_q =
        block_k = page) — the chunked prefill appends into scattered
        pages yet accumulates in the identical block order."""
        rng = np.random.default_rng(1)
        H, D, P = 2, 8, 12
        k, v, kp, vp, bt = _paged_layout(T, page, P, H, D, dtype, rng)
        q_full = rng.standard_normal((1, H, T, D)).astype(
            np.float32).astype(dtype)
        for c in range(-(-T // page)):
            t0 = c * page
            n_valid = min(page, T - t0)
            Tc = t0 + n_valid
            dense, _ = pa._flash_fwd_impl(
                jnp.asarray(q_full[:, :, :Tc]),
                jnp.asarray(k[:, :, :Tc]), jnp.asarray(v[:, :, :Tc]),
                True, page, page, need_lse=False)
            dense_rows = np.asarray(dense)[0, :, t0:Tc, :]
            qc = np.zeros((page, H, D), dtype)
            qc[:n_valid] = np.moveaxis(q_full[0, :, t0:Tc], 0, 1)
            out = pa.paged_flash_prefill(
                jnp.asarray(qc), jnp.asarray(kp), jnp.asarray(vp),
                bt, t0, n_valid)
            got = np.moveaxis(np.asarray(out)[:n_valid], 0, 1)
            assert np.array_equal(got.view(np.uint8),
                                  dense_rows.view(np.uint8)), \
                f"chunk {c} diverged from the dense kernel"


class TestMultiPagePrefill:
    """ISSUE 31: a chunk of n whole pages of one slot. Every query tile
    of one page sees the pages a page-sized chunk at its offset sees, in
    the same order, against the same blocks."""

    CHUNKS = [
        # pages in the chunk, valid rows (page 4, the chunk starts at 4)
        pytest.param(2, 8, id="2-pages-full"),
        pytest.param(2, 7, id="2-pages-ragged-last"),
        pytest.param(4, 16, id="4-pages-full"),
        pytest.param(4, 11, id="4-pages-ragged-and-a-padded-page"),
    ]

    @staticmethod
    def _layout(n, n_valid, dtype, seed):
        """A prompt of t0 + n_valid tokens scattered over the pool, its
        table widened by the chunk's padded pages and two entries more,
        all at the null page, which holds NaN: visiting it would leak
        0 x NaN into a carry."""
        rng = np.random.default_rng(seed)
        page, H, D, P = 4, 2, 8, 16
        t0 = page
        _, _, kp, vp, bt = _paged_layout(t0 + n_valid, page, P, H, D,
                                         dtype, rng)
        wide = np.zeros((1 + n + 2,), np.int32)
        wide[:bt.shape[0]] = bt
        assert np.all(wide[bt.shape[0]:] == 0) and 0 not in bt
        qc = rng.standard_normal((n * page, H, D)).astype(
            np.float32).astype(dtype)
        return page, t0, kp, vp, wide, qc

    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("n,n_valid", CHUNKS)
    def test_bitwise_the_same_rows_fed_page_by_page(self, n, n_valid,
                                                    dtype):
        page, t0, kp, vp, bt, qc = self._layout(n, n_valid, dtype, 10)
        kp[0] = np.nan
        vp[0] = np.nan
        got = np.asarray(pa.paged_flash_prefill(
            jnp.asarray(qc), jnp.asarray(kp), jnp.asarray(vp), bt, t0,
            n_valid))
        assert got.shape == qc.shape
        assert np.isfinite(got[:n_valid].astype(np.float32)).all()
        for j in range(-(-n_valid // page)):
            rows = slice(j * page, min((j + 1) * page, n_valid))
            want = np.asarray(pa.paged_flash_prefill(
                jnp.asarray(qc[j * page:(j + 1) * page]), jnp.asarray(kp),
                jnp.asarray(vp), bt, t0 + j * page,
                rows.stop - rows.start))[:rows.stop - rows.start]
            assert np.array_equal(_bits(got[rows]), _bits(want)), \
                f"query tile {j} is not the page-sized chunk's rows"

    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("n,n_valid", CHUNKS)
    def test_within_the_cores_rounding(self, n, n_valid, dtype):
        """Against ``paged_attend`` on the gathered table with all the
        chunk's rows at once: bitwise in bf16, a couple ulp in f32, as
        a page-sized chunk is (TestPagedAttendCore)."""
        page, t0, kp, vp, bt, qc = self._layout(n, n_valid, dtype, 11)
        got = np.asarray(pa.paged_flash_prefill(
            jnp.asarray(qc), jnp.asarray(kp), jnp.asarray(vp), bt, t0,
            n_valid))[:n_valid]
        ref = np.asarray(pa.paged_attend(
            jnp.asarray(qc[None]), jnp.asarray(kp)[bt[None]],
            jnp.asarray(vp)[bt[None]], jnp.asarray([t0 + n_valid]),
            jnp.asarray([t0])))[0, :n_valid]
        if dtype == jnp.bfloat16:
            assert np.array_equal(_bits(ref), _bits(got))
        else:
            err = np.max(np.abs(ref.astype(np.float64)
                                - got.astype(np.float64)))
            assert err <= 3e-7, f"core-vs-kernel {err}"

    def test_a_chunk_is_whole_pages(self):
        page, t0, kp, vp, bt, qc = self._layout(2, 7, np.float32, 12)
        with pytest.raises(ValueError, match="whole number of pages"):
            pa.paged_flash_prefill(jnp.asarray(qc[:page + 1]),
                                   jnp.asarray(kp), jnp.asarray(vp), bt,
                                   t0, page + 1)


# ----------------------------------------------------------------------
# the portable core (serving step functions)
# ----------------------------------------------------------------------

class TestPagedAttendCore:
    @pytest.mark.parametrize("T,page,dtype", GRIDS)
    def test_core_matches_kernels_page_order(self, T, page, dtype):
        """``paged_attend`` (what the transformer step twins trace)
        accumulates page-sequentially like the kernels. Against the
        prefill kernel, op for op the same: bitwise in bf16, a couple
        ulp in f32 (XLA fuses the f32 reductions slightly differently;
        the serving-parity gates compare like with like, so this
        tolerance never stacks). Against the decode kernel: its
        rounding (module docstring)."""
        rng = np.random.default_rng(3)
        H, D, P = 2, 8, 12
        _, _, kp, vp, bt = _paged_layout(T, page, P, H, D, dtype, rng)
        kpg = jnp.asarray(kp)[bt[None]]
        vpg = jnp.asarray(vp)[bt[None]]
        q = rng.standard_normal((1, H, D)).astype(np.float32).astype(dtype)
        sls = np.asarray([T], np.int32)
        out = np.asarray(pa.paged_flash_decode(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            bt[None], sls))
        ref = np.asarray(pa.paged_attend(
            jnp.asarray(q[:, None]), kpg, vpg, jnp.asarray(sls),
            jnp.asarray(sls) - 1))[:, 0]
        _assert_within_rounding(out, ref, dtype, "decode kernel vs core")
        qc = rng.standard_normal((page, H, D)).astype(
            np.float32).astype(dtype)
        for c in range(-(-T // page)):
            t0 = c * page
            n_valid = min(page, T - t0)
            out = np.asarray(pa.paged_flash_prefill(
                jnp.asarray(qc), jnp.asarray(kp), jnp.asarray(vp), bt,
                t0, n_valid))[:n_valid]
            ref = np.asarray(pa.paged_attend(
                jnp.asarray(qc[None]), kpg, vpg,
                jnp.asarray([t0 + n_valid]), jnp.asarray([t0])))[0, :n_valid]
            if dtype == jnp.bfloat16:
                assert np.array_equal(ref.view(np.uint8),
                                      out.view(np.uint8)), f"chunk {c}"
            else:
                err = np.max(np.abs(ref.astype(np.float64)
                                    - out.astype(np.float64)))
                assert err <= 3e-7, f"chunk {c}: core-vs-kernel {err}"


# ----------------------------------------------------------------------
# ISSUE 27: live pages only, the whole pool, the dispatcher
# ----------------------------------------------------------------------

class TestLivePagesOnly:
    """Table entries past a slot's last live page point at a page full
    of NaN. A masked page would leak 0 x NaN into the carry; only a
    page that is never visited leaves the output bitwise alone."""

    @pytest.mark.parametrize("T,page,dtype", GRIDS)
    def test_decode_never_reads_a_dead_page(self, T, page, dtype):
        rng = np.random.default_rng(4)
        H, D, P = 2, 8, 12
        _, _, kp, vp, bt = _paged_layout(T, page, P, H, D, dtype, rng,
                                         start_page=2)
        S, MP = 3, bt.shape[0] + 3
        bts = np.zeros((S, MP), np.int32)
        bts[0, :bt.shape[0]] = bt
        bts[1, :bt.shape[0]] = bt
        sls = np.asarray([T, T - page + 1, 0], np.int32)
        q = rng.standard_normal((S, H, D)).astype(np.float32).astype(dtype)
        clean = pa.paged_flash_decode(jnp.asarray(q), jnp.asarray(kp),
                                      jnp.asarray(vp), bts, sls)
        kp[1] = np.nan
        vp[1] = np.nan
        dead = bts.copy()
        for s in range(S):
            dead[s, -(-int(sls[s]) // page):] = 1
        got = pa.paged_flash_decode(jnp.asarray(q), jnp.asarray(kp),
                                    jnp.asarray(vp), dead, sls)
        assert np.isfinite(np.asarray(got, np.float32)).all()
        assert np.array_equal(_bits(got), _bits(clean))

    @pytest.mark.parametrize("T,page,dtype", GRIDS)
    def test_prefill_never_reads_a_dead_page(self, T, page, dtype):
        rng = np.random.default_rng(5)
        H, D, P = 2, 8, 12
        _, _, kp, vp, bt = _paged_layout(T, page, P, H, D, dtype, rng,
                                         start_page=2)
        MP = bt.shape[0] + 2
        wide = np.zeros((MP,), np.int32)
        wide[:bt.shape[0]] = bt
        qc = rng.standard_normal((page, H, D)).astype(
            np.float32).astype(dtype)
        kn, vn = kp.copy(), vp.copy()
        kn[1] = np.nan
        vn[1] = np.nan
        for c in range(-(-T // page)):
            t0 = c * page
            n_valid = min(page, T - t0)
            clean = pa.paged_flash_prefill(
                jnp.asarray(qc), jnp.asarray(kp), jnp.asarray(vp), wide,
                t0, n_valid)
            dead = wide.copy()
            dead[c + 1:] = 1
            got = pa.paged_flash_prefill(
                jnp.asarray(qc), jnp.asarray(kn), jnp.asarray(vn), dead,
                t0, n_valid)
            assert np.isfinite(np.asarray(got, np.float32)[:n_valid]).all()
            assert np.array_equal(_bits(got)[:n_valid],
                                  _bits(clean)[:n_valid]), f"chunk {c}"


class TestWholePool:
    @pytest.mark.parametrize("T,page,dtype", GRIDS)
    def test_layer_index_equals_the_layers_slice(self, T, page, dtype):
        """The pool passed whole with a layer index — static or traced
        — is bitwise the same call on that layer's slice, decode and
        prefill."""
        rng = np.random.default_rng(6)
        H, D, P, L = 2, 8, 12, 3
        layers = [_paged_layout(T, page, P, H, D, dtype, rng)
                  for _ in range(L)]
        bt = layers[1][4]
        kps = jnp.asarray(np.stack([l[2] for l in layers]))
        vps = jnp.asarray(np.stack([l[3] for l in layers]))
        q = jnp.asarray(rng.standard_normal((1, H, D)).astype(
            np.float32).astype(dtype))
        qc = jnp.asarray(rng.standard_normal((page, H, D)).astype(
            np.float32).astype(dtype))
        sls = np.asarray([T], np.int32)
        t0 = page * ((T - 1) // page)
        for li in (1, jnp.asarray(1, jnp.int32)):
            whole = pa.paged_flash_decode(q, kps, vps, bt[None], sls,
                                          layer=li)
            sliced = pa.paged_flash_decode(q, kps[1], vps[1], bt[None], sls)
            assert np.array_equal(_bits(whole), _bits(sliced))
            whole = pa.paged_flash_prefill(qc, kps, vps, bt, t0, T - t0,
                                           layer=li)
            sliced = pa.paged_flash_prefill(qc, kps[1], vps[1], bt, t0,
                                            T - t0)
            assert np.array_equal(_bits(whole), _bits(sliced))
        with pytest.raises(ValueError, match="layer"):
            pa.paged_flash_decode(q, kps, vps, bt[None], sls)


class TestDispatcher:
    """`paged_attention` chooses from the backend and the shapes alone."""

    RULE = [
        # page, H, Dh, itemsize, admitted
        pytest.param(128, 16, 128, 2, True, id="benchmark-bf16"),
        pytest.param(8, 8, 128, 4, True, id="tiny-f32"),
        pytest.param(16, 16, 64, 2, False, id="chip-smoke-lm-Dh64"),
        pytest.param(128, 8, 128, 2, False, id="bf16-H-half-a-tile"),
        pytest.param(8, 16, 128, 2, False, id="bf16-page-half-a-tile"),
        pytest.param(512, 16, 128, 2, False, id="page-over-the-VMEM-budget"),
    ]

    @pytest.mark.parametrize("page,H,Dh,itemsize,admitted", RULE)
    def test_shape_rule(self, page, H, Dh, itemsize, admitted):
        assert pa._paged_kernel_fits(page, H, Dh, itemsize) is admitted

    @pytest.mark.parametrize("page,H,Dh,itemsize,admitted", RULE)
    def test_a_refused_shape_goes_to_paged_attend_an_admitted_never(
            self, monkeypatch, page, H, Dh, itemsize, admitted):
        dtype = jnp.bfloat16 if itemsize == 2 else jnp.float32
        monkeypatch.setattr(pa, "_on_tpu", lambda: True)
        want = "pallas" if admitted else "reference"
        assert pa.paged_attention_impl(page, H, Dh, dtype) == want
        monkeypatch.setattr(pa, "_on_tpu", lambda: False)
        assert pa.paged_attention_impl(page, H, Dh, dtype) == "reference"

    @pytest.mark.parametrize("impl,lengths,want", [
        pytest.param("pallas", [1, 8, 9, 24], 1 + 1 + 2 + 3, id="live-pages"),
        pytest.param("pallas", 17, 3, id="one-slot"),
        pytest.param("reference", [1, 8, 9, 24], 4 * 5, id="whole-tables"),
        pytest.param("reference", 17, 5, id="whole-table-one-slot"),
    ])
    def test_pages_visited_is_the_kernels_rule(self, impl, lengths, want):
        """What the scheduler's spans report: the pages `p * page <
        length` admits on the kernel path, every table entry on the
        reference path."""
        got = pa.paged_pages_visited(impl, np.asarray(lengths), 8, 5)
        assert got == want and isinstance(got, int)

    @pytest.mark.parametrize("on_tpu", [False, True])
    def test_dispatcher_calls_what_the_rule_says(self, monkeypatch, on_tpu):
        """On the TPU branch an admitted shape traces the kernels and
        never paged_attend; off it, paged_attend on the gathered
        tables; the two agree to a rounding."""
        rng = np.random.default_rng(8)
        L, P, page, H, Dh, S, MP = 2, 9, 8, 8, 128, 2, 3
        kps, vps = (jnp.asarray(rng.standard_normal(
            (L, P, page, H, Dh)).astype(np.float32)) for _ in range(2))
        bts = np.asarray([[3, 5, 1], [2, 7, 0]], np.int32)
        sls = np.asarray([19, 9], np.int32)
        q = jnp.asarray(rng.standard_normal((S, 1, H, Dh)).astype(
            np.float32))
        want = pa.paged_attend(q, kps[1][bts], vps[1][bts],
                               jnp.asarray(sls), jnp.asarray(sls) - 1)
        called = []
        real = pa.paged_attend
        monkeypatch.setattr(
            pa, "paged_attend",
            lambda *a, **k: called.append("reference") or real(*a, **k))
        monkeypatch.setattr(pa, "_on_tpu", lambda: on_tpu)
        got = pa.paged_attention(q, kps, vps, 1, bts, jnp.asarray(sls),
                                 jnp.asarray(sls) - 1)
        assert called == ([] if on_tpu else ["reference"])
        # Dh 128: sixteen times the parity grids' terms in every sum
        _assert_within_rounding(got, want, np.float32, "dispatcher",
                                wider=8)
        qc = jnp.asarray(rng.standard_normal((1, page, H, Dh)).astype(
            np.float32))
        got = pa.paged_attention(qc, kps, vps, 0, bts[:1],
                                 jnp.asarray([19]), jnp.asarray([16]))
        assert called == ([] if on_tpu else ["reference"] * 2)
        want = real(qc, kps[0][bts[:1]], vps[0][bts[:1]],
                    jnp.asarray([19]), jnp.asarray([16]))
        # the prefill kernel is the core op for op: a couple ulp in f32
        err = np.max(np.abs(np.asarray(got, np.float64)[0, :3]
                            - np.asarray(want, np.float64)[0, :3]))
        assert err <= 1e-6, f"dispatcher, chunk: {err}"
        # a chunk of two pages of one slot goes the same way
        qc2 = jnp.asarray(rng.standard_normal((1, 2 * page, H, Dh)).astype(
            np.float32))
        got = pa.paged_attention(qc2, kps, vps, 0, bts[:1],
                                 jnp.asarray([19]), jnp.asarray([8]))
        assert called == ([] if on_tpu else ["reference"] * 3)
        want = real(qc2, kps[0][bts[:1]], vps[0][bts[:1]],
                    jnp.asarray([19]), jnp.asarray([8]))
        err = np.max(np.abs(np.asarray(got, np.float64)[0, :11]
                            - np.asarray(want, np.float64)[0, :11]))
        assert err <= 1e-6, f"dispatcher, chunk of two pages: {err}"
        if on_tpu:                  # neither a row a slot nor whole pages
            with pytest.raises(ValueError, match="whole number of pages"):
                pa.paged_attention(qc2[:, :page + 1], kps, vps, 0, bts[:1],
                                   jnp.asarray([19]), jnp.asarray([8]))
            with pytest.raises(ValueError, match="one chunk of one slot"):
                pa.paged_attention(jnp.concatenate([qc2, qc2]), kps, vps, 0,
                                   bts, jnp.asarray(sls), jnp.asarray([8, 0]))


class TestServedThroughTheKernels:
    def test_scheduler_serves_the_reference_paths_tokens(self, monkeypatch):
        """A CausalTransformerLM at a tiny shape the rule admits, the
        dispatcher steered to the kernels (interpret mode) from here:
        PagedSequenceScheduler serves the tokens of the paged_attend
        path and its logits to the kernels' tolerance — padded slots
        (3 requests in a bucket of 4) and tail chunks included — and
        the dense serial oracle, through the same dispatcher, agrees
        with the served tokens."""
        from deeplearning4j_tpu.nn.transformer import (
            CausalTransformerLM, dense_serial_trajectory)
        from deeplearning4j_tpu.serving import (PagedSequenceScheduler,
                                                greedy_sampler, stream_rng)

        cfg = dict(vocab=37, d_model=1024, n_heads=8, n_layers=2,
                   d_ff=256, max_context=32, page_size=8,
                   dtype="float32", seed=5)
        rng = np.random.default_rng(9)
        prompts = [rng.integers(0, 37, n).tolist() for n in (11, 8, 3)]

        def serve(on_tpu):
            monkeypatch.setattr(pa, "_on_tpu", lambda: on_tpu)
            model = CausalTransformerLM(**cfg)
            sched = PagedSequenceScheduler(
                model, num_pages=16, slot_buckets=(4,),
                start_thread=False, prefix_sharing=False)
            reqs = [sched.submit(p, max_new_tokens=4, wait=False)
                    for p in prompts]
            sched.drain()
            out = [(r.wait(1.0).tolist(), r.logits) for r in reqs]
            sched.close()
            return model, sched._attend, out

        _, impl, ref = serve(False)
        assert impl == "reference"
        model, impl, got = serve(True)
        assert impl == "pallas"
        for (toks, logits), (rtoks, rlogits) in zip(got, ref):
            assert toks == rtoks
            np.testing.assert_allclose(logits, rlogits, rtol=0, atol=2e-5)
        # the oracle takes the same kernels: token equality as on the chip
        for i, (p, (toks, _)) in enumerate(zip(prompts, got)):
            want, _ = dense_serial_trajectory(
                model, p, 4, greedy_sampler(), stream_rng(0, i), bucket=4)
            assert toks == want

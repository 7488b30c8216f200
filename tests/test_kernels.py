"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True on CPU; same kernels compile natively on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import enable_x64

pytestmark = pytest.mark.slow  # interpret=True Pallas sweeps

from repro.kernels import (
    decode_attention_op,
    flash_attention,
    rglru_scan_op,
    ssd_scan_op,
)
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ref import (
    reference_attention,
    reference_decode_attention,
    reference_rglru_scan,
    reference_ssd_scan,
)

TOL = dict(atol=2e-2, rtol=2e-2)      # bf16 sweeps
TOL32 = dict(atol=2e-5, rtol=2e-5)    # f32 sweeps


def tols(dtype):
    return TOL if dtype == jnp.bfloat16 else TOL32


class TestFlashAttention:
    @pytest.mark.parametrize("S,H,Hkv,D", [
        (128, 4, 4, 64),     # MHA
        (256, 8, 2, 64),     # GQA 4:1
        (192, 8, 1, 32),     # MQA, ragged seq (pads)
        (256, 4, 4, 128),    # wider head
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_sweep(self, S, H, Hkv, D, dtype):
        q = jax.random.normal(jax.random.key(1), (2, S, H, D), dtype)
        k = jax.random.normal(jax.random.key(2), (2, S, Hkv, D), dtype)
        v = jax.random.normal(jax.random.key(3), (2, S, Hkv, D), dtype)
        out = flash_attention_fwd(q, k, v, block_q=64, block_k=64, interpret=True)
        ref = reference_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), **tols(dtype))

    @pytest.mark.parametrize("window", [16, 64])
    def test_sliding_window(self, window):
        q = jax.random.normal(jax.random.key(1), (1, 256, 4, 32))
        k = jax.random.normal(jax.random.key(2), (1, 256, 1, 32))
        v = jax.random.normal(jax.random.key(3), (1, 256, 1, 32))
        out = flash_attention_fwd(q, k, v, window=window, block_q=64,
                                  block_k=64, interpret=True)
        ref = reference_attention(q, k, v, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL32)

    def test_custom_vjp_matches_reference_grad(self):
        q = jax.random.normal(jax.random.key(1), (1, 64, 2, 32))
        k = jax.random.normal(jax.random.key(2), (1, 64, 2, 32))
        v = jax.random.normal(jax.random.key(3), (1, 64, 2, 32))
        g1 = jax.grad(lambda q: flash_attention(q, k, v).sum())(q)
        g2 = jax.grad(lambda q: reference_attention(q, k, v).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), **TOL32)


class TestDecodeAttention:
    @pytest.mark.parametrize("C,H,Hkv,D", [
        (96, 8, 2, 64), (128, 4, 1, 32), (100, 4, 4, 64),
    ])
    def test_partial_cache_and_masks(self, C, H, Hkv, D):
        B = 2
        q = jax.random.normal(jax.random.key(1), (B, H, D))
        kc = jax.random.normal(jax.random.key(2), (B, C, Hkv, D))
        vc = jax.random.normal(jax.random.key(3), (B, C, Hkv, D))
        pos = jnp.broadcast_to(jnp.arange(C)[None], (B, C)).astype(jnp.int32)
        pos = pos.at[:, int(0.8 * C):].set(-1)
        cur = jnp.array([int(0.5 * C), int(0.7 * C)], jnp.int32)
        out = decode_attention_op(q, kc, vc, pos, cur)
        ref = reference_decode_attention(q, kc, vc, pos, cur)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL32)

    def test_window_masking(self):
        B, C, H, D = 1, 64, 2, 32
        q = jax.random.normal(jax.random.key(1), (B, H, D))
        kc = jax.random.normal(jax.random.key(2), (B, C, 1, D))
        vc = jax.random.normal(jax.random.key(3), (B, C, 1, D))
        pos = jnp.arange(C)[None].astype(jnp.int32)
        cur = jnp.array([60], jnp.int32)
        from repro.kernels.decode_attention import decode_attention_kernel_call
        out = decode_attention_kernel_call(q, kc, vc, pos, cur, window=16,
                                           interpret=True)
        ref = reference_decode_attention(q, kc, vc, pos, cur, window=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL32)


class TestRglruScan:
    @pytest.mark.parametrize("B,T,C", [(2, 200, 96), (1, 64, 128), (3, 130, 64)])
    def test_sweep(self, B, T, C):
        a = jax.nn.sigmoid(jax.random.normal(jax.random.key(4), (B, T, C)))
        b = jax.random.normal(jax.random.key(5), (B, T, C))
        h0 = jax.random.normal(jax.random.key(6), (B, C))
        out = rglru_scan_op(a, b, h0)
        ref = reference_rglru_scan(a, b, h0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)

    def test_zero_state_start(self):
        a = jnp.full((1, 32, 16), 0.5)
        b = jnp.ones((1, 32, 16))
        out = rglru_scan_op(a, b, None)
        ref = reference_rglru_scan(a, b, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


class TestSsdScan:
    @pytest.mark.parametrize("S,H,P,N,chunk", [
        (96, 4, 16, 32, 32), (128, 2, 32, 16, 64), (100, 4, 16, 32, 32),
    ])
    def test_sweep(self, S, H, P, N, chunk):
        B = 2
        x = jax.random.normal(jax.random.key(7), (B, S, H, P)) * 0.5
        A = -jnp.abs(jax.random.normal(jax.random.key(8), (B, S, H))) * 0.1
        Bm = jax.random.normal(jax.random.key(9), (B, S, N)) * 0.5
        Cm = jax.random.normal(jax.random.key(10), (B, S, N)) * 0.5
        y = ssd_scan_op(x, A, Bm, Cm, chunk=chunk)
        yref, _ = reference_ssd_scan(x, A, Bm, Cm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                                   atol=1e-4, rtol=1e-4)

    def test_matches_model_ssd_chunked(self):
        """Kernel == the model's chunked SSD (same math, different tiling)."""
        from repro.models.ssd import ssd_chunked
        B, S, H, P, N = 1, 64, 2, 16, 32
        x = jax.random.normal(jax.random.key(7), (B, S, H, P)) * 0.5
        A = -jnp.abs(jax.random.normal(jax.random.key(8), (B, S, H))) * 0.1
        Bm = jax.random.normal(jax.random.key(9), (B, S, N)) * 0.5
        Cm = jax.random.normal(jax.random.key(10), (B, S, N)) * 0.5
        y_kernel = ssd_scan_op(x, A, Bm, Cm, chunk=32)
        y_model, _ = ssd_chunked(x, A, Bm[:, :, None, :], Cm[:, :, None, :], 32)
        np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                                   atol=1e-4, rtol=1e-4)


RTOL_BII = 1e-10   # the established betaincinv tier (tests/test_betaincinv.py)


class TestBetaincinvPallas:
    """Tiled Pallas betaincinv vs the `jax.scipy`-based core path and
    scipy's ppf: <= 1e-10 relative on the acceptance grid (asserted in
    interpret mode — the gate every BENCH_kernels.json timing row sits
    behind), with the round-trip fallback for the handful of points where
    scipy's own iteration carries ~1e-10-scale error."""

    def test_grid_vs_core_and_scipy(self):
        from scipy import stats
        from repro.core.betainc import betaincinv
        from repro.kernels.betaincinv_pallas import betaincinv_kernel_call
        from test_betaincinv import GRID_AB, GRID_Q

        with enable_x64():
            A, B, Q = np.meshgrid(GRID_AB, GRID_AB, GRID_Q, indexing="ij")
            a, b, q = A.ravel(), B.ravel(), Q.ravel()
            ours = np.asarray(betaincinv_kernel_call(
                jnp.asarray(a), jnp.asarray(b), jnp.asarray(q),
                interpret=True))
            assert np.all(np.isfinite(ours))
            core = np.asarray(betaincinv(a, b, q))
            rel_core = np.abs(ours - core) / np.maximum(np.abs(core), 1e-300)
            assert rel_core.max() < RTOL_BII, rel_core.max()
            ref = stats.beta.ppf(q, a, b)
            rel = np.abs(ours - ref) / np.maximum(np.abs(ref), 1e-300)
            for (i,) in np.argwhere(rel >= RTOL_BII):
                ours_rt = abs(stats.beta.cdf(ours[i], a[i], b[i]) - q[i])
                ref_rt = abs(stats.beta.cdf(ref[i], a[i], b[i]) - q[i])
                assert ours_rt <= ref_rt, (a[i], b[i], q[i], ours[i], ref[i])

    def test_deep_tail_small_shape_parameters(self):
        """a, b << 1 with tail q: the in-kernel Lanczos lgamma (evaluated
        at z+1, stepped down) must keep the power-law initial guess and
        the bracketed iteration accurate at roots ~1e-160."""
        from scipy import stats
        from repro.kernels.betaincinv_pallas import betaincinv_kernel_call

        with enable_x64():
            cases = np.array([
                (0.05, 0.05, 1e-6), (0.05, 25.0, 1e-4), (0.1, 0.5, 1e-2),
                (25.0, 0.05, 1.0 - 1e-4), (0.5, 0.1, 1.0 - 1e-2),
                (0.02, 3.0, 0.3),
            ])
            a, b, q = cases.T
            ours = np.asarray(betaincinv_kernel_call(
                jnp.asarray(a), jnp.asarray(b), jnp.asarray(q),
                interpret=True))
            ref = stats.beta.ppf(q, a, b)
            rel = np.abs(ours - ref) / np.maximum(np.abs(ref), 1e-300)
            assert rel.max() < RTOL_BII, list(zip(cases, ours, ref))

    @pytest.mark.parametrize("n,block_n", [(7, 4), (16, 16), (33, 8),
                                           (5, 1024)])
    def test_tiling_and_padding_inert(self, n, block_n):
        """Any (n, block_n) tiling — ragged tiles padded with inert
        (a=1, b=1, q=0.5) lanes — returns exactly the untiled result."""
        from repro.kernels.betaincinv_pallas import betaincinv_kernel_call

        with enable_x64():
            rng = np.random.default_rng(n * 31 + block_n)
            a = jnp.asarray(rng.uniform(0.1, 40.0, n))
            b = jnp.asarray(rng.uniform(0.1, 40.0, n))
            q = jnp.asarray(rng.uniform(1e-6, 1 - 1e-6, n))
            tiled = betaincinv_kernel_call(a, b, q, block_n=block_n,
                                           interpret=True)
            whole = betaincinv_kernel_call(a, b, q, block_n=max(n, 1),
                                           interpret=True)
            np.testing.assert_array_equal(np.asarray(tiled),
                                          np.asarray(whole))

    def test_core_use_pallas_dispatch(self):
        """betaincinv(use_pallas=True) broadcasts, ravels through the
        kernel and reshapes back — same tier vs the default path."""
        from repro.core.betainc import betaincinv

        with enable_x64():
            a = np.array([[0.5, 2.0, 8.0]])
            b = np.array([[1.5], [3.0]])
            q = 0.1
            base = np.asarray(betaincinv(a, b, q))
            pallas = np.asarray(betaincinv(a, b, q, use_pallas=True))
            assert pallas.shape == base.shape == (2, 3)
            rel = np.abs(pallas - base) / np.maximum(np.abs(base), 1e-300)
            assert rel.max() < RTOL_BII

    def test_batch_lower_bound_use_pallas(self):
        """The §7.5 fleet entry point: batch_lower_bound(use_pallas=True)
        stays on the <= 1e-10 tier vs the default XLA inversion."""
        from repro.core.batch_decision import batch_lower_bound

        with enable_x64():
            rng = np.random.default_rng(17)
            a = rng.uniform(0.2, 30.0, 128)
            b = rng.uniform(0.2, 30.0, 128)
            base = batch_lower_bound(a, b, 0.1)
            pallas = batch_lower_bound(a, b, 0.1, use_pallas=True)
            rel = np.abs(pallas - base) / np.maximum(np.abs(base), 1e-300)
            assert rel.max() < RTOL_BII

    def test_empty_input(self):
        from repro.kernels.betaincinv_pallas import betaincinv_kernel_call

        out = betaincinv_kernel_call(jnp.zeros(0), jnp.zeros(0),
                                     jnp.zeros(0), interpret=True)
        assert out.shape == (0,)

    def test_drift_monitor_use_pallas_trigger_parity(self):
        """Trigger 2 through the kernel inversion: identical trigger
        events to the default XLA batch path on the same fleet (away
        from razor-edge bounds — the documented interleaving caveat)."""
        from repro.core.drift import DriftMonitor

        with enable_x64():
            rng = np.random.default_rng(23)
            R = 40
            edges = [("u", f"v{i}") for i in range(R)]
            a = rng.uniform(0.5, 40.0, R)
            b = rng.uniform(0.5, 40.0, R)
            al = rng.uniform(0.0, 1.0, R)
            C = rng.uniform(0.001, 0.05, R)
            L = rng.uniform(0.01, 2.0, R)
            events = []
            for use_pallas in (False, True):
                mon = DriftMonitor(credible_consecutive_n=2)
                evs = []
                for _ in range(3):
                    evs.append(mon.check_credible_bound_batch(
                        edges, a, b, al, C, L, use_pallas=use_pallas))
                events.append(evs)
            for e0, e1 in zip(*events):
                assert [x is None for x in e0] == [x is None for x in e1]
                for x0, x1 in zip(e0, e1):
                    if x0 is not None:
                        assert x0.edge == x1.edge
                        assert x0.action == x1.action


def _random_tick_case(seed, N=16, Bp=8, S=8, *, dt=np.float64):
    """A randomized SoA row table + request/settle buckets for the fused
    tick: kill-switch bits cleared on some rows, drift runs seeded near
    the trigger N, duplicate settle rows, -1 padding sentinels."""
    rng = np.random.default_rng(seed)
    post = jnp.asarray(rng.uniform(0.5, 30.0, (N, 2)), dt)
    rowcfg = jnp.asarray(np.stack([
        np.full(N, 0.1),                      # gamma
        rng.uniform(0.9, 1.0, N),             # discount
        rng.uniform(0.0, 0.6, N),             # trigger-2 floor
    ], 1), dt)
    flags = jnp.asarray(np.stack([
        rng.integers(0, 2, N),                # kill-switch bits
        rng.integers(0, 4, N),                # breach runs near N=3
    ], 1).astype(np.int32))
    nreq = rng.integers(1, Bp + 1)
    row = np.full(Bp, -1, np.int32)
    row[:nreq] = rng.integers(0, N, nreq)
    reqs = np.zeros((Bp, 7), dt)
    reqs[:nreq] = np.stack([
        rng.uniform(0.0, 1.0, nreq),          # alpha
        rng.uniform(0.01, 2.0, nreq),         # lambda
        rng.uniform(0.05, 3.0, nreq),         # latency
        rng.integers(10, 2000, nreq),         # in_tok
        rng.integers(10, 2000, nreq),         # out_tok
        np.full(nreq, 3e-6),                  # in_price
        np.full(nreq, 15e-6),                 # out_price
    ], 1)
    nset = rng.integers(0, S + 1)
    out_row = np.full(S, -1, np.int32)
    # duplicates on purpose: same-row settles must compose in order
    out_row[:nset] = rng.integers(0, max(N // 2, 1), nset)
    out_x = np.zeros(S, dt)
    out_x[:nset] = rng.integers(0, 2, nset)
    return post, rowcfg, flags, jnp.asarray(row), jnp.asarray(reqs), \
        jnp.asarray(out_row), jnp.asarray(out_x)


class TestOnlineTickKernel:
    """Fused settle + D4 gate + drift vs `OnlineDecisionService._tick_impl`:
    the mean path is bitwise-f64 (the traced-runtime-zero FMA pin survives
    the Pallas lowering); the lower-bound path sits at the <= 1e-10
    betaincinv tier with decisions still expected to agree away from
    razor-edge thresholds."""

    @staticmethod
    def _reference(post, rowcfg, flags, row, reqs, out_row, out_x, cn,
                   *, use_lower_bound, check_drift):
        import repro.core.online as ol

        state = ol.ServiceState(
            post=post, rowcfg=rowcfg, flags=flags,
            roll=jnp.ones((post.shape[0], 6), jnp.int32),
            tel=jnp.zeros((32, len(ol.TELEMETRY_FIELDS)), post.dtype))
        # the JITTED tick, exactly as the service dispatches it: calling
        # _tick_impl eagerly would bake `zero` into the settle scan as a
        # constant, XLA would fold the `+ zero` pin away and contract
        # `b*d + (1-x)` into one fma — a 1-ULP-different reference that
        # no real tick ever produces
        return ol._tick(
            state, post.dtype.type(0.0), row, row, reqs,
            jnp.zeros((0, 1), post.dtype), jnp.zeros(0, jnp.int32),
            out_row, out_x, np.int32(cn), jnp.ones(9, jnp.int32),
            use_lower_bound=use_lower_bound, check_drift=check_drift,
            use_rollout=False, use_beam=False)

    @pytest.mark.parametrize("seed", range(6))
    @pytest.mark.parametrize("block_n", [4, 16, 1024])
    def test_mean_path_bitwise(self, seed, block_n):
        from repro.kernels.online_tick import online_tick_kernel_call

        with enable_x64():
            post, rowcfg, flags, row, reqs, out_row, out_x = \
                _random_tick_case(seed)
            cn = 3
            st, rows, bools, trig, _, _ = self._reference(
                post, rowcfg, flags, row, reqs, out_row, out_x, cn,
                use_lower_bound=False, check_drift=True)
            (p2, f2, pu, pm, ev, thr, cs, lv, fl, er, tg) = \
                online_tick_kernel_call(
                    post, rowcfg, flags, jnp.asarray(0.0, post.dtype),
                    row, reqs, out_row, out_x, np.int32(cn),
                    use_lower_bound=False, check_drift=True,
                    block_n=block_n, interpret=True)
            np.testing.assert_array_equal(np.asarray(st.post),
                                          np.asarray(p2), "post")
            np.testing.assert_array_equal(np.asarray(st.flags),
                                          np.asarray(f2), "flags")
            np.testing.assert_array_equal(np.asarray(trig),
                                          np.asarray(tg) > 0, "trig")
            cols = np.asarray(rows)
            np.testing.assert_array_equal(cols[:, 2], np.asarray(pu))
            np.testing.assert_array_equal(cols[:, 3], np.asarray(pm))
            np.testing.assert_array_equal(cols[:, 4], np.asarray(ev))
            np.testing.assert_array_equal(cols[:, 5], np.asarray(thr))
            np.testing.assert_array_equal(cols[:, 7], np.asarray(cs))
            np.testing.assert_array_equal(cols[:, 8], np.asarray(lv))
            b = np.asarray(bools)
            np.testing.assert_array_equal(b[:, 0], np.asarray(fl) > 0)
            np.testing.assert_array_equal(b[:, 1], np.asarray(er) > 0)

    @pytest.mark.parametrize("seed", range(3))
    def test_lower_bound_tier(self, seed):
        from repro.kernels.online_tick import online_tick_kernel_call

        with enable_x64():
            post, rowcfg, flags, row, reqs, out_row, out_x = \
                _random_tick_case(100 + seed)
            st, rows, bools, trig, _, _ = self._reference(
                post, rowcfg, flags, row, reqs, out_row, out_x, 3,
                use_lower_bound=True, check_drift=True)
            (p2, f2, pu, pm, ev, thr, cs, lv, fl, er, tg) = \
                online_tick_kernel_call(
                    post, rowcfg, flags, jnp.asarray(0.0, post.dtype),
                    row, reqs, out_row, out_x, np.int32(3),
                    use_lower_bound=True, check_drift=True,
                    block_n=8, interpret=True)
            # settle is bitwise regardless of the gate's quantile path
            np.testing.assert_array_equal(np.asarray(st.post),
                                          np.asarray(p2))
            cols = np.asarray(rows)
            rel = np.abs(cols[:, 2] - np.asarray(pu)) / np.maximum(
                np.abs(cols[:, 2]), 1e-300)
            assert rel.max() < RTOL_BII
            # P_mean column stays bitwise (no inversion on it)
            np.testing.assert_array_equal(cols[:, 3], np.asarray(pm))
            # decisions agree (thresholds are not razor-edge in this vector)
            np.testing.assert_array_equal(
                np.asarray(bools)[:, 0], np.asarray(fl) > 0)

    def test_drift_breach_run_accumulates_and_triggers(self):
        """Rows seeded one breach short of N: a touching request must
        tick the run to N, trigger, clear the kill-switch bit and reset
        the run — bitwise the `_tick_impl` drift block."""
        from repro.kernels.online_tick import online_tick_kernel_call

        with enable_x64():
            N = 8
            dt = np.float64
            post = jnp.asarray(np.tile([1.0, 9.0], (N, 1)), dt)  # mean 0.1
            rowcfg = jnp.asarray(np.stack([
                np.full(N, 0.1), np.ones(N),
                np.full(N, 0.9),                   # floor far above P_low
            ], 1), dt)
            flags = jnp.asarray(np.stack([
                np.ones(N), np.full(N, 2),         # run = N-1
            ], 1).astype(np.int32))
            row = jnp.asarray(np.array([0, 3, -1, -1], np.int32))
            reqs = jnp.asarray(np.tile(
                np.array([0.5, 1.0, 1.0, 100, 100, 3e-6, 15e-6]), (4, 1)))
            out_row = jnp.asarray(np.full(2, -1, np.int32))
            out_x = jnp.zeros(2, dt)
            st, _, _, trig, _, _ = self._reference(
                post, rowcfg, flags, row, reqs, out_row, out_x, 3,
                use_lower_bound=False, check_drift=True)
            (p2, f2, *_rest, tg) = online_tick_kernel_call(
                post, rowcfg, flags, jnp.asarray(0.0, dt), row, reqs,
                out_row, out_x, np.int32(3), use_lower_bound=False,
                check_drift=True, block_n=4, interpret=True)
            np.testing.assert_array_equal(np.asarray(st.flags),
                                          np.asarray(f2))
            np.testing.assert_array_equal(np.asarray(trig),
                                          np.asarray(tg) > 0)
            tgn = np.asarray(tg) > 0
            assert tgn[0] and tgn[3] and not tgn[1:3].any() \
                and not tgn[4:].any()

    def test_same_row_settles_compose_in_arrival_order(self):
        """Two settles on one row within a tick: the discount recurrence
        must apply them sequentially (a*d+x twice), not gather-last —
        bitwise vs the reference scan."""
        from repro.kernels.online_tick import online_tick_kernel_call

        with enable_x64():
            dt = np.float64
            post = jnp.asarray([[2.0, 3.0], [4.0, 5.0]], dt)
            rowcfg = jnp.asarray([[0.1, 0.9, 0.0], [0.1, 0.95, 0.0]], dt)
            flags = jnp.asarray(np.ones((2, 2), np.int32))
            row = jnp.asarray(np.full(1, -1, np.int32))
            reqs = jnp.zeros((1, 7), dt)
            out_row = jnp.asarray(np.array([0, 0, 1, 0], np.int32))
            out_x = jnp.asarray(np.array([1.0, 0.0, 1.0, 1.0], dt))
            st, *_ = self._reference(
                post, rowcfg, flags, row, reqs, out_row, out_x, 3,
                use_lower_bound=False, check_drift=False)
            p2 = online_tick_kernel_call(
                post, rowcfg, flags, jnp.asarray(0.0, dt), row, reqs,
                out_row, out_x, np.int32(3), use_lower_bound=False,
                check_drift=False, block_n=2, interpret=True)[0]
            np.testing.assert_array_equal(np.asarray(st.post),
                                          np.asarray(p2))


class TestInterpretResolution:
    """kernels.ops._interpret(): the default backend alone decides —
    cpu -> interpret, tpu -> native, anything else raises — with no
    environment override.  The regression pin for the rule, applied
    uniformly to replay_grid and the two kernel ops (all of which resolve
    the flag OUTSIDE jit and pass it as a static arg)."""

    def test_resolution_order(self, monkeypatch):
        from repro.kernels import ops

        assert ops._interpret() is True          # the CPU test backend
        monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
        assert ops._interpret() is False
        assert ops.on_tpu()
        for other in ("gpu", "cuda", "rocm", "METAL"):
            monkeypatch.setattr(ops.jax, "default_backend",
                                lambda other=other: other)
            with pytest.raises(RuntimeError, match="no Pallas lowering"):
                ops._interpret()

    def test_flag_not_baked_into_trace(self, monkeypatch):
        """The rule is re-read on every call: the jitted ops take
        `interpret` as a static arg resolved per call, so a backend with
        no lowering raises even after a CPU call traced and cached the
        interpret executable, and the CPU answer is unchanged after."""
        from repro.kernels import ops

        with enable_x64():
            a = jnp.asarray(np.array([2.0, 0.5]))
            b = jnp.asarray(np.array([3.0, 0.5]))
            q = jnp.asarray(np.array([0.1, 0.5]))
            first = np.asarray(ops.betaincinv_op(a, b, q))
            monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
            with pytest.raises(RuntimeError, match="no Pallas lowering"):
                ops.betaincinv_op(a, b, q)
            monkeypatch.undo()
            second = np.asarray(ops.betaincinv_op(a, b, q))
            np.testing.assert_array_equal(first, second)


class TestNativeDtypeGuard:
    """A native (TPU) lowering never sees f64: each kernel raises a clear
    error on f64 operands rather than casting them down, while the
    interpret path keeps taking f64 (the CPU parity tier)."""

    @staticmethod
    def _call(kernel, dt, interpret):
        n = 8
        x = jnp.full((n,), 0.5, dt)
        if kernel == "betaincinv":
            from repro.kernels.betaincinv_pallas import betaincinv_kernel_call
            return betaincinv_kernel_call(x + 1.0, x + 2.0, x,
                                          interpret=interpret)
        if kernel == "replay_grid":
            from repro.kernels.replay_grid import replay_grid_kernel_call
            return replay_grid_kernel_call(x, x, x, x[:3], x[:2],
                                           interpret=interpret)
        from repro.kernels.online_tick import online_tick_kernel_call
        return online_tick_kernel_call(
            jnp.ones((n, 2), dt), jnp.ones((n, 3), dt),
            jnp.ones((n, 2), jnp.int32), jnp.zeros((), dt),
            jnp.arange(4, dtype=jnp.int32), jnp.ones((4, 7), dt),
            jnp.full((2,), -1, jnp.int32), jnp.zeros((2,), dt),
            np.int32(3), interpret=interpret)

    @pytest.mark.parametrize("kernel",
                             ["betaincinv", "replay_grid", "online_tick"])
    def test_f64_native_raises_interpret_runs(self, kernel):
        with enable_x64():
            with pytest.raises(TypeError, match="float64"):
                self._call(kernel, jnp.float64, interpret=False)
            out = self._call(kernel, jnp.float64, interpret=True)
            assert jax.tree.leaves(out)[0].dtype == jnp.float64

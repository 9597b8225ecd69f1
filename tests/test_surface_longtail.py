"""Long-tail API surface: utils helpers, amp/autograd extras, fft
hermitian n-d, linalg tail, incubate extras, geometric sampling,
distribution trio, device module, quantization bases, text re-exports
(ref: the per-module __all__ lists in python/paddle/*)."""
import numpy as np
import pytest

import paddle_tpu as paddle


class TestUtils:
    def test_deprecated_levels(self):
        from paddle_tpu.utils import deprecated

        @deprecated(since="0.1", update_to="new_api", level=1)
        def old(x):
            return x + 1

        with pytest.warns(DeprecationWarning):
            assert old(1) == 2
        assert "Deprecated" in old.__doc__

        @deprecated(level=2)
        def gone():
            pass

        with pytest.raises(RuntimeError):
            gone()

    def test_run_check_and_versions(self, capsys):
        from paddle_tpu.utils import require_version, run_check, try_import
        run_check()
        assert "successfully" in capsys.readouterr().out
        require_version("0.0.1")
        with pytest.raises(Exception):
            require_version("999.0")
        assert try_import("math") is not None
        with pytest.raises(ImportError):
            try_import("definitely_not_a_module_xyz")


class TestAmpAutograd:
    def test_bf16_supported(self):
        assert paddle.amp.is_bfloat16_supported() is True
        assert isinstance(paddle.amp.is_float16_supported(), bool)

    def test_saved_tensors_hooks_pylayer(self):
        from paddle_tpu.autograd import PyLayer, saved_tensors_hooks
        packed, unpacked = [], []

        class Sq(PyLayer):
            @staticmethod
            def forward(ctx, x):
                ctx.save_for_backward(x)
                return x * x

            @staticmethod
            def backward(ctx, g):
                (x,) = ctx.saved_tensor()
                return g * 2 * x

        x = paddle.to_tensor(np.array([3.0], np.float32),
                             stop_gradient=False)
        with saved_tensors_hooks(
                lambda t: (packed.append(1), t.numpy())[-1],
                lambda p: (unpacked.append(1),
                           paddle.to_tensor(p))[-1]):
            y = Sq.apply(x)
        y.sum().backward()
        assert packed and unpacked
        np.testing.assert_allclose(x.grad.numpy(), [6.0])


class TestFFTHermitian:
    def test_hfft2_matches_composed_numpy(self, rng):
        x = (rng.normal(size=(4, 5)) + 1j * rng.normal(size=(4, 5)))
        x = x.astype(np.complex64)
        out = paddle.fft.hfft2(paddle.to_tensor(x)).numpy()
        want = np.fft.hfft(np.fft.fft(x, axis=-2), axis=-1)
        np.testing.assert_allclose(out, want, atol=1e-3)

    def test_ihfftn_roundtrips_hfftn(self, rng):
        real = rng.normal(size=(6, 8)).astype(np.float32)
        spec = paddle.fft.ihfftn(paddle.to_tensor(real))
        back = paddle.fft.hfftn(spec).numpy()
        np.testing.assert_allclose(back, real, atol=1e-3)


class TestLinalgTail:
    def test_inv_cond_norms_lu(self, rng):
        import paddle_tpu.linalg as L
        a_np = rng.normal(size=(5, 5)).astype(np.float32)
        a = paddle.to_tensor(a_np)
        np.testing.assert_allclose(L.inv(a).numpy(), np.linalg.inv(a_np),
                                   atol=1e-4)
        assert abs(float(L.cond(a).numpy())
                   - np.linalg.cond(a_np)) < 1e-2
        np.testing.assert_allclose(
            float(L.vector_norm(a).numpy()),
            np.linalg.norm(a_np.ravel()), rtol=1e-5)
        # keepdim with axis=None keeps every reduced dim as size-1
        kd = L.vector_norm(a, keepdim=True)
        assert kd.shape == [1, 1]
        np.testing.assert_allclose(float(kd.numpy()[0, 0]),
                                   np.linalg.norm(a_np.ravel()),
                                   rtol=1e-5)
        lu_m, piv = L.lu(a)
        P, Lo, U = L.lu_unpack(lu_m, piv)
        np.testing.assert_allclose(
            P.numpy() @ Lo.numpy() @ U.numpy(), a_np, atol=1e-4)

    def test_cholesky_inverse_and_matrix_exp(self, rng):
        import paddle_tpu.linalg as L
        a_np = rng.normal(size=(4, 4)).astype(np.float32)
        spd = a_np @ a_np.T + 4 * np.eye(4, dtype=np.float32)
        Lc = np.linalg.cholesky(spd)
        np.testing.assert_allclose(
            L.cholesky_inverse(paddle.to_tensor(Lc)).numpy(),
            np.linalg.inv(spd), atol=1e-3)
        np.testing.assert_allclose(
            L.matrix_exp(paddle.to_tensor(
                np.zeros((3, 3), np.float32))).numpy(),
            np.eye(3), atol=1e-6)

    def test_lowrank_factorizations(self, rng):
        import paddle_tpu.linalg as L
        paddle.seed(0)
        lowr = (rng.normal(size=(8, 2))
                @ rng.normal(size=(2, 6))).astype(np.float32)
        U, S, V = L.svd_lowrank(paddle.to_tensor(lowr), q=4)
        rec = U.numpy() @ np.diag(S.numpy()) @ V.numpy().T
        np.testing.assert_allclose(rec, lowr, atol=1e-3)
        U2, _, _ = L.pca_lowrank(paddle.to_tensor(lowr), q=3)
        assert U2.shape[1] == 3

    def test_fp8_gemm_contract(self, rng):
        import paddle_tpu.linalg as L
        a = paddle.to_tensor(rng.normal(size=(4, 4)).astype(np.float32))
        out = L.fp8_fp8_half_gemm_fused(a, a, act="relu")
        assert "bfloat16" in str(out.dtype)
        assert float(out.numpy().astype(np.float32).min()) >= 0


class TestIncubateExtras:
    def test_masked_softmax_and_identity_loss(self, rng):
        import paddle_tpu.incubate as inc
        x = paddle.to_tensor(rng.normal(size=(2, 4, 4)).astype(np.float32))
        m = paddle.to_tensor(np.zeros((2, 4, 4), np.float32))
        a = inc.softmax_mask_fuse(x, m).numpy()
        b = inc.softmax_mask_fuse_upper_triangle(x).numpy()
        assert np.allclose(a.sum(-1), 1, atol=1e-5)
        assert np.allclose(np.triu(b[0], 1), 0, atol=1e-6)
        assert abs(float(inc.identity_loss(x, "mean").numpy())
                   - x.numpy().mean()) < 1e-6

    def test_lookahead_trains(self, rng):
        import paddle_tpu.incubate as inc
        import paddle_tpu.nn as nn
        paddle.seed(0)
        mdl = nn.Linear(4, 4)
        opt = inc.LookAhead(
            paddle.optimizer.SGD(learning_rate=0.1,
                                 parameters=mdl.parameters()),
            alpha=0.5, k=2)
        X = paddle.to_tensor(rng.normal(size=(8, 4)).astype(np.float32))
        l0 = None
        for _ in range(6):
            loss = (mdl(X) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            l0 = l0 if l0 is not None else float(loss)
        assert float(loss) < l0

    def test_model_average_window_mean(self, rng):
        import paddle_tpu.incubate as inc
        import paddle_tpu.nn as nn
        paddle.seed(0)
        mdl = nn.Linear(4, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=mdl.parameters())
        X = paddle.to_tensor(rng.normal(size=(8, 4)).astype(np.float32))
        ma = inc.ModelAverage(0.5, parameters=mdl.parameters(),
                              min_average_window=10,
                              max_average_window=100)
        vals = []
        for _ in range(3):
            loss = (mdl(X) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            ma.step()
            vals.append(mdl.weight.numpy().copy())
        trained = mdl.weight.numpy().copy()
        with ma.apply():
            applied = mdl.weight.numpy().copy()
        np.testing.assert_allclose(mdl.weight.numpy(), trained)
        np.testing.assert_allclose(applied, np.mean(vals, axis=0),
                                   atol=1e-5)


class TestGeometricSampling:
    ROW = np.array([1, 2, 0, 2, 0, 1], np.int64)
    COLPTR = np.array([0, 2, 4, 6], np.int64)

    def test_sample_neighbors(self):
        import paddle_tpu.geometric as G
        n, c = G.sample_neighbors(
            paddle.to_tensor(self.ROW), paddle.to_tensor(self.COLPTR),
            paddle.to_tensor(np.array([0, 2], np.int64)))
        assert c.numpy().tolist() == [2, 2]
        assert sorted(n.numpy()[:2].tolist()) == [1, 2]

    def test_weighted_sample_respects_support(self):
        import paddle_tpu.geometric as G
        w = np.array([1.0, 0.0, 1.0, 1.0, 1.0, 1.0], np.float32)
        n, c = G.weighted_sample_neighbors(
            paddle.to_tensor(self.ROW), paddle.to_tensor(self.COLPTR),
            paddle.to_tensor(w),
            paddle.to_tensor(np.array([0], np.int64)), sample_size=1)
        assert n.numpy().tolist() == [1]  # the zero-weight edge never

    def test_send_uv_and_heter_reindex(self, rng):
        import paddle_tpu.geometric as G
        x = paddle.to_tensor(rng.normal(size=(3, 4)).astype(np.float32))
        uv = G.send_uv(x, x, paddle.to_tensor(np.array([0], np.int64)),
                       paddle.to_tensor(np.array([2], np.int64)), "sub")
        np.testing.assert_allclose(
            uv.numpy()[0], x.numpy()[0] - x.numpy()[2], atol=1e-6)
        src, dst, nodes = G.reindex_heter_graph(
            paddle.to_tensor(np.array([0, 1], np.int64)),
            [paddle.to_tensor(np.array([5, 6, 5], np.int64))],
            [paddle.to_tensor(np.array([2, 1], np.int64))])
        assert nodes.numpy().tolist() == [0, 1, 5, 6]
        assert src.numpy().tolist() == [2, 3, 2]
        assert dst.numpy().tolist() == [0, 0, 1]


class TestDistributionTrio:
    def test_continuous_bernoulli_moments_and_cdf(self):
        from paddle_tpu.distribution import ContinuousBernoulli
        paddle.seed(0)
        for p in (0.25, 0.7):
            cb = ContinuousBernoulli(p)
            xs = np.linspace(1e-4, 1 - 1e-4, 10001).astype(np.float32)
            pdf = cb.prob(paddle.to_tensor(xs)).numpy().astype(np.float64)
            Z = np.trapezoid(pdf, xs)
            m = np.trapezoid(pdf * xs, xs)
            v = np.trapezoid(pdf * (xs - m) ** 2, xs)
            assert abs(Z - 1) < 1e-3
            assert abs(float(cb.mean.numpy()) - m) < 1e-3
            assert abs(float(cb.variance.numpy()) - v) < 1e-3
            u = np.array([0.1, 0.5, 0.9], np.float32)
            x = cb.icdf(paddle.to_tensor(u))
            np.testing.assert_allclose(cb.cdf(x).numpy(), u, atol=1e-4)
        # Taylor patch at p=0.5 stays finite
        cb5 = ContinuousBernoulli(0.5)
        assert abs(float(cb5.mean.numpy()) - 0.5) < 1e-4

    def test_lkj_known_densities(self):
        from paddle_tpu.distribution import LKJCholesky
        paddle.seed(0)
        # dim=2: p(rho) = C (1-rho^2)^(eta-1); eta=1 -> uniform (1/2),
        # eta=2 -> 3/4 (1-rho^2)
        for eta, want_fn in ((1.0, lambda r: 0.5),
                             (2.0, lambda r: 0.75 * (1 - r * r))):
            lkj = LKJCholesky(2, eta)
            for rho in (-0.6, 0.0, 0.5):
                L = np.array([[1, 0], [rho, np.sqrt(1 - rho ** 2)]],
                             np.float32)
                lp = float(lkj.log_prob(paddle.to_tensor(L)).numpy())
                assert abs(lp - np.log(want_fn(rho))) < 5e-4

    def test_lkj_samples_are_correlation_cholesky(self):
        from paddle_tpu.distribution import LKJCholesky
        paddle.seed(0)
        Ls = LKJCholesky(3, 2.0).sample((200,)).numpy()
        corr = Ls @ np.swapaxes(Ls, -1, -2)
        np.testing.assert_allclose(
            np.diagonal(corr, axis1=-2, axis2=-1), 1.0, atol=1e-5)
        assert abs(corr[:, 1, 0].mean()) < 0.1


class TestDeviceModule:
    def test_streams_events_and_queries(self):
        import paddle_tpu.device as D
        assert "cpu" in D.get_all_device_type() or D.get_all_device_type()
        s = D.Stream()
        e = s.record_event()
        assert e.query() is True
        e.synchronize()
        with D.stream_guard(D.Stream()):
            pass
        D.synchronize()
        assert D.get_cudnn_version() is None
        assert D.is_compiled_with_rocm() is False
        with pytest.raises(RuntimeError):
            D.XPUPlace(0)

    def test_set_device_never_resolves_to_another_platform(self):
        """Asking for a TPU on a host without one raises (as does an
        index the host does not have); the current place is unchanged."""
        import paddle_tpu as paddle
        assert paddle.set_device("cpu:0").jax_device().platform == "cpu"
        for missing in ("tpu", "tpu:0", "cpu:4096"):
            with pytest.raises(RuntimeError):
                paddle.set_device(missing)
        with pytest.raises(RuntimeError):
            paddle.TPUPlace(0).jax_device()
        assert paddle.get_device() == "cpu:0"


class TestQuantBase:
    def test_quanter_factory(self):
        from paddle_tpu.quantization import BaseQuanter, quanter

        @quanter("MyQuanterFactory")
        class MyQuanter(BaseQuanter):
            def __init__(self, bits=8):
                super().__init__()
                self.bits = bits

            def forward(self, x):
                return x

            def bit_length(self):
                return self.bits

        import sys
        factory_cls = getattr(sys.modules[MyQuanter.__module__],
                              "MyQuanterFactory")
        inst = factory_cls(bits=4)._instance()
        assert isinstance(inst, MyQuanter) and inst.bit_length() == 4


class TestTextSurface:
    def test_dataset_names_reexported(self):
        import paddle_tpu.text as t
        for n in ("Conll05st", "Imdb", "Imikolov", "Movielens",
                  "UCIHousing", "WMT14", "WMT16"):
            assert hasattr(t, n), n


class TestReviewRegressions:
    def test_khop_revisited_frontier_dst_ids(self):
        """Hop-2 edges from a revisited node must use its EXISTING id
        (reindex-by-position corrupted them)."""
        import paddle_tpu.incubate as inc
        row = np.array([1, 0, 0], np.int64)
        colptr = np.array([0, 2, 3], np.int64)
        src, dst, nodes, cnt = inc.graph_khop_sampler(
            paddle.to_tensor(row), paddle.to_tensor(colptr),
            paddle.to_tensor(np.array([0], np.int64)), [-1, -1])
        n = len(nodes.numpy())
        assert dst.numpy().max() < n and src.numpy().max() < n
        # hop 1: node 0 -> {1, 0}; hop 2 dst ids must be the ids of 1
        # and 0 themselves (1 and 0), never a fresh id
        assert set(dst.numpy().tolist()) <= {0, 1}

    def test_ormqr_nonsquare_full_q(self, rng):
        import scipy.linalg as sl
        import paddle_tpu.linalg as L
        a_np = rng.normal(size=(4, 2)).astype(np.float32)
        (h, tau), _ = sl.qr(a_np, mode="raw")
        y = rng.normal(size=(4, 3)).astype(np.float32)
        out = L.ormqr(paddle.to_tensor(h.astype(np.float32)),
                      paddle.to_tensor(tau.astype(np.float32)),
                      paddle.to_tensor(y))
        q_full, _ = sl.qr(a_np, mode="full")
        # sign conventions match because both use the same reflectors
        np.testing.assert_allclose(out.numpy(), q_full @ y, atol=1e-4)

    def test_fp8_gemm_bias_before_act(self):
        import paddle_tpu.linalg as L
        eye = paddle.to_tensor(np.eye(3, dtype=np.float32))
        out = L.fp8_fp8_half_gemm_fused(
            eye, eye, bias=paddle.to_tensor(
                np.full((3,), -5.0, np.float32)), act="relu")
        # relu(I @ I - 5) == 0 everywhere; act-then-bias would give -4/-5
        assert float(out.numpy().astype(np.float32).min()) == 0.0

    def test_incubate_graph_signature_order(self):
        """Reference positional order: (row, colptr, nodes, eids,
        perm_buffer, sample_size)."""
        import paddle_tpu.incubate as inc
        row = np.array([1, 2, 0, 2, 0, 1], np.int64)
        colptr = np.array([0, 2, 4, 6], np.int64)
        n, c = inc.graph_sample_neighbors(
            paddle.to_tensor(row), paddle.to_tensor(colptr),
            paddle.to_tensor(np.array([0], np.int64)), None, None, 1)
        assert c.numpy().tolist() == [1]
        out = inc.graph_send_recv(
            paddle.to_tensor(np.eye(3, dtype=np.float32)),
            paddle.to_tensor(np.array([0, 1], np.int64)),
            paddle.to_tensor(np.array([1, 2], np.int64)), "sum")
        assert out.shape == [3, 3]

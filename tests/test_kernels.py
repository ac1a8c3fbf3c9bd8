"""Per-kernel correctness: Pallas (interpret mode) vs the pure-jnp oracles,
sweeping shapes/dtypes via hypothesis (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.embedding_bag.embedding_bag import embedding_bag_pallas
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.segsum.ops import segment_sum_sorted
from repro.kernels.segsum.ref import segment_sum_sorted_ref


def tol_for(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


class TestSegSum:
    @settings(max_examples=12, deadline=None)
    @given(e=st.integers(1, 3000), d=st.integers(1, 160),
           n=st.integers(1, 700), seed=st.integers(0, 10**6),
           dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
    def test_matches_oracle(self, e, d, n, seed, dtype):
        rng = np.random.default_rng(seed)
        recv = np.sort(rng.integers(0, n, e)).astype(np.int32)
        msgs = jnp.asarray(rng.normal(size=(e, d)), dtype)
        out = segment_sum_sorted(msgs, recv, n, interpret=True)
        # ground truth accumulates in f32 (the kernel does too; the bf16
        # oracle itself loses precision on long segments — taxonomy Part E)
        truth = np.asarray(segment_sum_sorted_ref(
            msgs.astype(jnp.float32), jnp.asarray(recv), n))
        err = np.abs(np.asarray(out, np.float32) - truth).max()
        scale = np.abs(truth).max() + 1e-6
        limit = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        assert err / scale < limit, (err, scale)

    def test_empty_rows_are_zero(self):
        msgs = jnp.ones((8, 16), jnp.float32)
        recv = np.asarray([3] * 8, np.int32)
        out = segment_sum_sorted(msgs, recv, 10, interpret=True)
        assert float(out[3].sum()) == pytest.approx(8 * 16)
        rest = jnp.asarray([0, 1, 2, 4, 5, 6, 7, 8, 9])
        assert float(jnp.abs(out[rest]).sum()) == 0.0

    def test_power_law_degree_distribution(self):
        """Skewed receivers (hot rows) — the GraphLab workload."""
        rng = np.random.default_rng(0)
        recv = np.sort(np.minimum(
            (rng.pareto(1.2, 4000) * 5).astype(np.int32), 99))
        msgs = jnp.asarray(rng.normal(size=(4000, 64)), jnp.float32)
        out = segment_sum_sorted(msgs, recv, 100, interpret=True)
        ref = segment_sum_sorted_ref(msgs, jnp.asarray(recv), 100)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


    def test_tpu_dispatch_takes_lane_multiples_only(self, monkeypatch):
        """The width rule of segment_combine's TPU dispatch: a sorted sum
        whose width is a multiple of 128 lanes goes to the kernel, a
        narrower one (LBP's [E, K] messages) stays on XLA's segment_sum
        instead of being padded to 128 lanes."""
        from repro.core.graph import segment_combine
        from repro.kernels.segsum import ops

        calls = []
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(ops, "segment_sum_sorted",
                            lambda m, r, n: calls.append(m.shape[1]) or
                            segment_sum_sorted_ref(m, jnp.asarray(r), n))
        recv = np.asarray([0, 0, 1, 3], np.int32)
        for width in (1, 2, 100, 128, 256):
            msgs = jnp.ones((4, width), jnp.float32)
            out = segment_combine(msgs, jnp.asarray(recv), 4,
                                  receivers_np=recv)
            np.testing.assert_array_equal(np.asarray(out)[:, 0],
                                          [2, 1, 0, 1])
        assert calls == [128, 256]


class TestFlashAttention:
    @settings(max_examples=10, deadline=None)
    @given(b=st.integers(1, 3), s=st.integers(8, 400),
           kv=st.sampled_from([1, 2, 4]), group=st.sampled_from([1, 2, 4]),
           d=st.sampled_from([64, 128]), causal=st.booleans(),
           seed=st.integers(0, 10**6),
           dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
    def test_matches_oracle(self, b, s, kv, group, d, causal, seed, dtype):
        h = kv * group
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)
        k = jnp.asarray(rng.normal(size=(b, s, kv, d)), dtype)
        v = jnp.asarray(rng.normal(size=(b, s, kv, d)), dtype)
        out = flash_attention_pallas(q, k, v, causal=causal, interpret=True)
        ref = attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **tol_for(dtype))

    @settings(max_examples=6, deadline=None)
    @given(s=st.integers(64, 300), window=st.integers(8, 64),
           seed=st.integers(0, 10**6))
    def test_sliding_window(self, s, window, seed):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(1, s, 4, 64)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, s, 2, 64)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, s, 2, 64)), jnp.float32)
        out = flash_attention_pallas(q, k, v, causal=True,
                                     sliding_window=window, interpret=True)
        ref = attention_ref(q, k, v, causal=True, sliding_window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_long_kv_streaming(self):
        """KV far longer than one block: the online softmax must rescale."""
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 2048, 2, 64)) * 3, jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 2048, 2, 64)), jnp.float32)
        out = flash_attention_pallas(q, k, v, causal=False, interpret=True)
        ref = attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)


class TestEmbeddingBag:
    @settings(max_examples=10, deadline=None)
    @given(v=st.integers(16, 3000), d=st.sampled_from([16, 64, 128]),
           b=st.integers(1, 300), h=st.integers(1, 6),
           seed=st.integers(0, 10**6),
           dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
    def test_matches_oracle(self, v, d, b, h, seed, dtype):
        rng = np.random.default_rng(seed)
        table = jnp.asarray(rng.normal(size=(v, d)), dtype)
        ids = jnp.asarray(rng.integers(0, v, (b, h)), jnp.int32)
        out = embedding_bag_pallas(table, ids, interpret=True)
        ref = embedding_bag_ref(table, ids)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            **tol_for(dtype))

    def test_repeated_ids_in_bag(self):
        table = jnp.asarray(np.eye(8, 4), jnp.float32)
        ids = jnp.asarray([[2, 2, 2]], jnp.int32)
        out = embedding_bag_pallas(table, ids, interpret=True)
        np.testing.assert_allclose(np.asarray(out)[0],
                                   3 * np.eye(8, 4)[2])

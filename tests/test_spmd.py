"""SPMD TD-Orch tests: single-device numerics vs the dense oracle, drop
behavior under capacity pressure (push vs push-pull), contention detection,
and multi-device shard_map equivalence (subprocess with 4 host devices)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core.spmd import (
    MoEDispatchConfig,
    bucket_routing,
    detect_contention,
    gather_from_buckets,
    moe_direct_pull,
    moe_direct_push,
    moe_push_pull,
    moe_reference,
    scatter_to_buckets,
    select_hot,
)


def _workload(seed, T=64, d=16, f=32, E=8, k=2, hot_expert=3, bias=3.0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    w_in = jnp.asarray(rng.normal(size=(E, d, 2 * f)) * 0.1, jnp.float32)
    w_out = jnp.asarray(rng.normal(size=(E, f, d)) * 0.1, jnp.float32)
    logits = rng.normal(size=(T, E))
    if hot_expert is not None:
        logits[:, hot_expert] += bias
    top = np.argsort(-logits, axis=1)[:, :k]
    gates = np.full((T, k), 1.0 / k)
    return x, jnp.asarray(top, jnp.int32), jnp.asarray(gates, jnp.float32), \
        w_in, w_out


class TestDispatchEngines:
    def test_push_pull_matches_dense_with_ample_capacity(self):
        x, ti, tg, wi, wo = _workload(0)
        ref = moe_reference(x, ti, tg, wi, wo)
        cfg = MoEDispatchConfig(num_experts=8, top_k=2, capacity_factor=8.0,
                                num_hot=2, ep_size=1)
        y, aux = jax.jit(lambda *a: moe_push_pull(*a, cfg))(x, ti, tg, wi, wo)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)
        assert int(aux.dropped_assignments) == 0

    def test_pull_baseline_exact(self):
        x, ti, tg, wi, wo = _workload(1)
        ref = moe_reference(x, ti, tg, wi, wo)
        cfg = MoEDispatchConfig(num_experts=8, top_k=2, ep_size=1)
        y, _ = moe_direct_pull(x, ti, tg, wi, wo, cfg)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)

    def test_hot_expert_rescued_from_drops(self):
        """§3.3 in MoE form: tight capacity drops most of the hot expert's
        tokens under direct-push; push-pull serves them via replication."""
        x, ti, tg, wi, wo = _workload(2, bias=5.0)
        tight = MoEDispatchConfig(num_experts=8, top_k=2,
                                  capacity_factor=0.4, num_hot=2, ep_size=1)
        y_pp, aux_pp = moe_push_pull(x, ti, tg, wi, wo, tight)
        y_dp, aux_dp = moe_direct_push(x, ti, tg, wi, wo, tight)
        assert int(aux_dp.dropped_assignments) > 20
        assert int(aux_pp.dropped_assignments) < \
            int(aux_dp.dropped_assignments) // 3

    def test_contention_histogram_exact(self):
        _, ti, _, _, _ = _workload(3)
        counts = detect_contention(ti, 8)
        want = np.bincount(np.asarray(ti).ravel(), minlength=8)
        np.testing.assert_array_equal(np.asarray(counts), want)

    def test_select_hot_threshold(self):
        counts = jnp.array([100, 1, 0, 50, 2, 0, 0, 0], jnp.int32)
        hot_ids, lookup, valid = select_hot(counts, 2, min_count=10)
        assert set(np.asarray(hot_ids).tolist()) == {0, 3}
        assert int(lookup[0]) >= 0 and int(lookup[3]) >= 0
        assert int(lookup[1]) == -1

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 500), k=st.sampled_from([1, 2, 4]),
           E=st.sampled_from([4, 8, 16]))
    def test_property_push_pull_vs_dense(self, seed, k, E):
        x, ti, tg, wi, wo = _workload(seed, E=E, k=k,
                                      hot_expert=seed % E, bias=4.0)
        ref = moe_reference(x, ti, tg, wi, wo)
        cfg = MoEDispatchConfig(num_experts=E, top_k=k, capacity_factor=16.0,
                                num_hot=min(2, E), ep_size=1)
        y, aux = moe_push_pull(x, ti, tg, wi, wo, cfg)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)


class TestRoutingPrimitives:
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 1000), nb=st.integers(1, 8),
           cap=st.integers(1, 40), n=st.integers(1, 100))
    def test_scatter_gather_roundtrip(self, seed, nb, cap, n):
        rng = np.random.default_rng(seed)
        dest = jnp.asarray(rng.integers(0, nb, n), jnp.int32)
        active = jnp.asarray(rng.random(n) < 0.9)
        rows = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
        routing = bucket_routing(dest, nb, cap, active)
        buf = scatter_to_buckets(rows, routing, nb, cap)
        back = gather_from_buckets(buf, routing, n)
        # kept rows come back exactly; dropped/inactive come back 0
        inv = np.zeros(n, np.int64)
        inv[np.asarray(routing.order)] = np.arange(n)
        kept = np.asarray(routing.keep)[inv]
        np.testing.assert_allclose(np.asarray(back)[kept],
                                   np.asarray(rows)[kept], atol=1e-6)
        assert (np.asarray(back)[~kept] == 0).all()

    def test_capacity_respected(self):
        dest = jnp.zeros(100, jnp.int32)
        routing = bucket_routing(dest, 4, 10, jnp.ones(100, bool))
        assert int(routing.keep.sum()) == 10


@pytest.mark.slow
def test_multidevice_shard_map_equivalence():
    """Push-pull under a real 4-way expert-parallel shard_map must equal the
    dense single-device oracle (subprocess: needs >1 host device)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.spmd import MoEDispatchConfig, moe_push_pull, moe_reference
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("model",))
        rng = np.random.default_rng(1)
        T, d, f, E, k, ep = 128, 16, 32, 8, 2, 4
        x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
        w_in = jnp.asarray(rng.normal(size=(E, d, 2*f)) * 0.1, jnp.float32)
        w_out = jnp.asarray(rng.normal(size=(E, f, d)) * 0.1, jnp.float32)
        logits = rng.normal(size=(T, E)); logits[:, 5] += 4.0
        top = np.argsort(-logits, axis=1)[:, :k]
        gates = np.full((T, k), 0.5)
        ti = jnp.asarray(top, jnp.int32); tg = jnp.asarray(gates, jnp.float32)
        ref = moe_reference(x, ti, tg, w_in, w_out)
        cfg = MoEDispatchConfig(num_experts=E, top_k=k, capacity_factor=4.0,
                                num_hot=2, axis_name="model", ep_size=ep)
        fn = jax.jit(jax.shard_map(
            lambda *a: moe_push_pull(*a, cfg)[0], mesh=mesh,
            in_specs=(P("model"), P("model"), P("model"), P("model"),
                      P("model")),
            out_specs=P("model")))
        y = fn(x, ti, tg, w_in, w_out)
        assert np.allclose(np.asarray(y), np.asarray(ref), atol=1e-4)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                         capture_output=True, text=True, timeout=300)
    assert "OK" in out.stdout, out.stderr[-2000:]

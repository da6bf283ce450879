"""Distributed multi-bank selection (shard_map + psum) vs monolithic.

Runs in a subprocess so we can set XLA_FLAGS for 8 host devices without
perturbing the rest of the test session (which must see 1 device).
"""

import subprocess
import sys
import textwrap


def test_sharded_topk_matches_monolithic():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        smap = jax.shard_map
        from repro.core.distsort import topk_mask_sharded, global_min_sharded
        from repro.core.topk import topk_mask, to_sortable_uint

        mesh = jax.make_mesh((8,), ("banks",))
        f = smap(lambda xl: topk_mask_sharded(xl, 13, "banks"),
                 mesh=mesh, in_specs=P(None, "banks"),
                 out_specs=P(None, "banks"))
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(4, 512)).astype(np.float32))
        assert np.array_equal(np.asarray(jax.jit(f)(x)), np.asarray(topk_mask(x, 13)))
        # heavy ties
        x = jnp.asarray(np.repeat(rng.normal(size=(2, 64)).astype(np.float32), 8, -1))
        m = np.asarray(jax.jit(f)(x))
        assert (m.sum(-1) == 13).all()
        assert np.array_equal(m, np.asarray(topk_mask(x, 13)))
        # global min == paper's multi-bank min search
        g = smap(lambda ul: global_min_sharded(ul, "banks"),
                 mesh=mesh, in_specs=P(None, "banks"), out_specs=P(None))
        u = to_sortable_uint(x)
        assert np.array_equal(np.asarray(jax.jit(g)(u)), np.asarray(u.min(-1)))
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=".", timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_collectives_property_match_numpy_oracle():
    """Property check on a 4-device mesh: kth_largest_sharded and
    global_min_sharded equal the single-device numpy oracle across seeded
    shapes, k values, and distributions (uniform / heavy-duplicate /
    adversarial all-equal)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, "src")
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.core.distsort import (
            global_min_sharded, kth_largest_sharded, topk_mask_sharded)

        mesh = jax.make_mesh((4,), ("banks",))
        rng = np.random.default_rng(7)

        def run_kth(u, k):
            f = shard_map(lambda ul: kth_largest_sharded(ul, k, "banks"),
                          mesh=mesh, in_specs=P(None, "banks"),
                          out_specs=P(None))
            return np.asarray(jax.jit(f)(jnp.asarray(u)))

        def run_min(u):
            g = shard_map(lambda ul: global_min_sharded(ul, "banks"),
                          mesh=mesh, in_specs=P(None, "banks"),
                          out_specs=P(None))
            return np.asarray(jax.jit(g)(jnp.asarray(u)))

        for trial in range(12):
            b = int(rng.integers(1, 5))
            n = int(rng.choice([8, 32, 128, 512]))
            kind = trial % 3
            if kind == 0:          # full-range uniform
                u = rng.integers(0, 1 << 32, (b, n), dtype=np.uint64)
            elif kind == 1:        # heavy duplicates (ties at threshold)
                u = rng.integers(0, 7, (b, n), dtype=np.uint64)
            else:                  # adversarial: every element equal
                u = np.full((b, n), int(rng.integers(0, 1 << 32)), np.uint64)
            u = u.astype(np.uint32)
            for k in {1, 2, n // 2, n - 1, n} - {0}:
                want = np.sort(u, axis=-1)[:, -k]
                got = run_kth(u, k)
                assert np.array_equal(got, want), (trial, k, got, want)
            assert np.array_equal(run_min(u), u.min(-1)), trial
            # exactly-k selection survives arbitrary tie mass at threshold
            m = np.asarray(jax.jit(shard_map(
                lambda xl: topk_mask_sharded(xl, 5, "banks"), mesh=mesh,
                in_specs=P(None, "banks"), out_specs=P(None, "banks")))(
                    jnp.asarray(u)))
            assert (m.sum(-1) == np.minimum(5, n)).all(), trial
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=".", timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout

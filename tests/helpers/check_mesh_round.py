"""Multi-device check of the FedHAP mesh round (run via subprocess with
XLA_FLAGS forcing 8 host devices — see tests/test_fedhap_mesh.py).

Exits nonzero (assertion) on any mismatch. Covers:
  1. faithful ring == numpy reference (segment weights + Eq. 16);
  2. fused round == faithful round (paper and exact modes);
  3. exact+global == true FedAvg weighted mean under any full coverage;
  4. Eq. 15 gating freezes replicas when an orbit has no visible sat;
  5. multi-pod (2 pods) faithful HAP chain == pod psum.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)


import jax

import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import full_aggregate, segment_upload_weights
from repro.core.dissemination import ConstellationMeshMap
from repro.core.mesh_round import FedRoundConfig, build_round


def tree_allclose(a, b, atol=1e-5):
    ok = jax.tree.map(
        lambda x, y: np.allclose(np.asarray(x), np.asarray(y), atol=atol),
        a, b)
    assert all(jax.tree.leaves(ok)), "tree mismatch"


def ex(params):
    """Per-satellite example tree (drop the leading S dim)."""
    import jax
    return jax.tree.map(lambda x: x[0], params)


def make_params(key, n_sats):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w": jax.random.normal(k1, (n_sats, 6, 4)),
        "b": jax.random.normal(k2, (n_sats, 4)),
        "nested": {"t": jax.random.normal(k3, (n_sats, 3))},
    }


def numpy_reference(params, sizes, visible, cmap, mode, orbit_weighting):
    """Timeline-style reference: per-orbit segments -> Eq. 16."""
    per_orbit = {}
    covered_all = True
    for l in range(cmap.n_orbits * cmap.n_pods):
        lo = l * cmap.sats_per_orbit
        hi = lo + cmap.sats_per_orbit
        vis = np.asarray(visible[lo:hi])
        sz = np.asarray(sizes[lo:hi], dtype=np.float64)
        lam, seg_end, seg_mass = segment_upload_weights(vis, sz, mode)
        if (seg_end < 0).all():
            covered_all = False
            continue
        parts = []
        for end in np.unique(seg_end):
            m = seg_end == end
            model = jax.tree.map(
                lambda x: np.tensordot(lam[m],
                                       np.asarray(x[lo:hi])[m], axes=1),
                params)
            parts.append((float(seg_mass[m][0]), model))
        per_orbit[l] = parts
    if not covered_all:
        return None
    return full_aggregate(per_orbit, orbit_weighting)


def run_single_pod():
    cmap = ConstellationMeshMap(n_orbits=2, sats_per_orbit=4, n_pods=1)
    mesh = jax.make_mesh((8, 1), ("data", "model"))
    n = cmap.total_sats
    params = make_params(jax.random.key(0), n)
    rng = np.random.default_rng(3)

    for trial in range(6):
        visible = rng.random(n) < 0.45
        for l in range(cmap.n_orbits):  # ensure coverage
            seg = slice(l * 4, l * 4 + 4)
            if not visible[seg].any():
                visible[l * 4 + rng.integers(4)] = True
        sizes = rng.uniform(1, 20, size=n)
        vis_j = jnp.asarray(visible)
        sz_j = jnp.asarray(sizes, jnp.float32)

        for mode in ("paper", "exact"):
            cfg = FedRoundConfig(cmap=cmap, partial_mode=mode,
                                 orbit_weighting="paper",
                                 ship_global_echo=(mode == "paper"))
            with jax.set_mesh(mesh):
                faithful = jax.jit(build_round(mesh, cfg, ex(params),
                                               kind="fedhap"))
                fused = jax.jit(build_round(mesh, cfg, ex(params),
                                            kind="fedhap_fused"))
                new_f, stats_f = faithful(params, sz_j, vis_j)
                new_u, stats_u = fused(params, sz_j, vis_j)
            assert float(stats_f["gate"]) == 1.0, stats_f
            # (1) faithful == numpy reference
            ref = numpy_reference(params, sizes, visible, cmap, mode,
                                  "paper")
            ref_stacked = jax.tree.map(
                lambda r: np.broadcast_to(r, (n,) + r.shape), ref)
            tree_allclose(new_f, ref_stacked)
            # (2) fused == faithful
            tree_allclose(new_u, new_f)

        # (3) exact + global weighting == true FedAvg mean
        cfg = FedRoundConfig(cmap=cmap, partial_mode="exact",
                             orbit_weighting="global",
                             ship_global_echo=False)
        with jax.set_mesh(mesh):
            rd = jax.jit(build_round(mesh, cfg, ex(params), kind="fedhap"))
            new_e, _ = rd(params, sz_j, vis_j)
            fa = jax.jit(build_round(mesh, cfg, ex(params), kind="fedavg"))
            new_avg, _ = fa(params, sz_j, vis_j)
        tree_allclose(new_e, new_avg, atol=1e-4)

    # (4) gating: orbit 1 fully invisible -> params unchanged.
    visible = np.zeros(n, bool)
    visible[:4] = True
    cfg = FedRoundConfig(cmap=cmap)
    with jax.set_mesh(mesh):
        rd = jax.jit(build_round(mesh, cfg, ex(params), kind="fedhap"))
        new_p, stats = rd(params, jnp.ones(n), jnp.asarray(visible))
    assert float(stats["gate"]) == 0.0
    tree_allclose(new_p, params)
    print("single-pod checks OK")


def run_multi_pod():
    cmap = ConstellationMeshMap(n_orbits=1, sats_per_orbit=2, n_pods=2)
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    n = cmap.total_sats  # 4
    params = make_params(jax.random.key(5), n)
    rng = np.random.default_rng(7)
    visible = np.array([True, False, True, True])
    sizes = rng.uniform(1, 9, size=n)
    vis_j, sz_j = jnp.asarray(visible), jnp.asarray(sizes, jnp.float32)

    for mode in ("paper", "exact"):
        ref = None
        for hap_ring in (True, False):
            cfg = FedRoundConfig(cmap=cmap, partial_mode=mode,
                                 hap_ring=hap_ring, ship_global_echo=False)
            with jax.set_mesh(mesh):
                rd = jax.jit(build_round(mesh, cfg, ex(params), kind="fedhap"))
                new_p, stats = rd(params, sz_j, vis_j)
            assert float(stats["gate"]) == 1.0
            if ref is None:
                ref = new_p
                # also compare against the numpy reference
                npref = numpy_reference(params, sizes, visible, cmap, mode,
                                        "paper")
                tree_allclose(new_p, jax.tree.map(
                    lambda r: np.broadcast_to(r, (n,) + r.shape), npref))
            else:
                # (5) HAP chain == pod psum
                tree_allclose(new_p, ref)
    print("multi-pod checks OK")


if __name__ == "__main__":
    assert jax.device_count() == 8, jax.device_count()
    run_single_pod()
    run_multi_pod()
    print("ALL MESH ROUND CHECKS PASSED")

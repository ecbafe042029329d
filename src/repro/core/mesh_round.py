"""FedHAP's hierarchical round as mesh collectives (shard_map).

Per-satellite model replicas carry a leading `S` dim sharded over the
`data` (and `pod`) mesh axes; inside `shard_map` each device holds one
satellite's shard (further sharded over `model` on the trailing dims).

Three rounds are provided:

- ``fedhap_round`` (faithful): the paper's Algorithm 1 —
  K-hop `ppermute` rings per orbit performing Eq.-14 partial aggregation
  at each invisible hop (optionally echoing the global model alongside,
  as the paper's dissemination does), masked Eq.-16 collection at each
  pod's HAP, sink->source `ppermute` chain over the pod axis, and the
  source HAP's broadcast back. Round gating (Eq. 15 coverage) keeps the
  old replicas when any satellite is uncovered.

- ``fedhap_round_fused`` (beyond-paper): algebraically identical update
  computed from closed-form chain weights (`segment_upload_weights` math
  inlined as mesh ops): tiny scalar all_gathers first, then ONE weighted
  psum of the model over `data` (+`pod`). Collective payload drops from
  O(K x model) to one all-reduce. Property-tested equal to the faithful
  round.

- ``fedavg_round``: the baseline star-topology aggregation (plain
  weighted all-reduce).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.dissemination import (
    ConstellationMeshMap,
    hap_chain_down,
    hap_chain_up,
)
from repro.core.weights import chain_stats
from repro.kernels.ops import fold_stacked_tree


@dataclasses.dataclass(frozen=True)
class FedRoundConfig:
    cmap: ConstellationMeshMap = ConstellationMeshMap()
    partial_mode: str = "paper"        # paper | exact   (Eq. 14 gamma)
    orbit_weighting: str = "paper"     # paper | global  (Eq. 16)
    hap_ring: bool = True              # faithful pod chain vs pod psum
    ship_global_echo: bool = True      # ring hops carry w^beta too (§III-B2)


def _tree_select(pred, a, b):
    """where(pred, a, b) on pytrees, broadcasting a scalar bool pred."""
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _tree_scale(tree, s):
    return jax.tree.map(lambda x: (x.astype(jnp.float32) * s), tree)


def _tree_add(a, b):
    return jax.tree.map(lambda x, y: x + y, a, b)


def _tree_ppermute(tree, axis, perm):
    return jax.tree.map(lambda x: jax.lax.ppermute(x, axis, perm), tree)


def _tree_psum(tree, axes):
    return jax.tree.map(lambda x: jax.lax.psum(x, axes), tree)


def _squeeze0(tree):
    return jax.tree.map(lambda x: jnp.squeeze(x, 0), tree)


def _expand0(tree):
    return jax.tree.map(lambda x: x[None], tree)


# ===================================================================
def sharded_fold(stacked_local, weights_local, axes=("data",),
                 use_pallas: Optional[bool] = None):
    """The production round's collective aggregation tail, factored out
    for any per-device satellite shard: a local weighted fold of the
    ``(S_local, ...)`` stacked shard through the shared backend dispatch
    (Pallas ``fedagg`` on accelerators, einsum ``tree_combine`` on CPU —
    :func:`repro.kernels.ops.fold_stacked_tree`) followed by ONE weighted
    ``psum`` over the mesh ``axes``. Must run inside ``shard_map``.

    With one satellite per device (``S_local == 1``) this is exactly
    ``fedhap_round_fused``'s ``contrib + psum`` tail (`_fused_body`);
    with larger shards it is the simulator megastep's sharded fold
    (:class:`repro.sim.executor.FusedExecutor`) — launch/ and sim/ share
    this one code path. Zero-weight rows (padded dead satellites)
    contribute exactly zero through both backends.
    """
    part = fold_stacked_tree(
        jax.tree.map(lambda x: x.astype(jnp.float32), stacked_local),
        weights_local, use_pallas)
    return _tree_psum(part, axes)


# ===================================================================
def _ring_phase(w, m_self, vis_self, m_orbit, cfg: FedRoundConfig):
    """Intra-orbit dissemination + Eq.-14 partial aggregation.

    Everything here is per-device (inside shard_map). Returns
    (upload_tree, up_mass, up_count, has_upload) — the partial-global
    model delivered to this slot if this slot is a visible satellite.
    """
    k = cfg.cmap.sats_per_orbit
    perm = cfg.cmap.ring_permutation(+1)
    axis = "data"
    zero = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), w)
    w32 = jax.tree.map(lambda x: x.astype(jnp.float32), w)

    outbox, out_mass = w32, m_self
    out_count = jnp.ones((), jnp.float32)
    ready = vis_self
    received = jnp.zeros((), bool)
    upload, up_mass = zero, jnp.zeros(())
    up_count = jnp.zeros(())
    has_upload = jnp.zeros((), bool)
    # The paper's hops also carry the global model w^beta (already
    # resident at every device — shipping it is pure communication, which
    # we reproduce for byte-faithfulness when ship_global_echo is set).
    echo = w32

    for _ in range(k):
        inbox = _tree_ppermute(outbox, axis, perm)
        if cfg.ship_global_echo:
            echo = _tree_ppermute(echo, axis, perm)
        in_mass = jax.lax.ppermute(out_mass, axis, perm)
        in_count = jax.lax.ppermute(out_count, axis, perm)
        in_ready = jax.lax.ppermute(ready, axis, perm)

        accept = in_ready & ~received
        received = received | accept
        # --- invisible satellite: fold own model (Eq. 14) and forward.
        if cfg.partial_mode == "paper":
            gamma = m_self / m_orbit
        else:  # exact running weighted mean
            gamma = m_self / (in_mass + m_self)
        folded = jax.tree.map(
            lambda acc, mine: (1.0 - gamma) * acc + gamma * mine,
            inbox, w32)
        take_fold = accept & ~vis_self
        outbox = _tree_select(take_fold, folded, outbox)
        out_mass = jnp.where(take_fold, in_mass + m_self, out_mass)
        out_count = jnp.where(take_fold, in_count + 1.0, out_count)
        ready = take_fold
        # --- visible satellite: the chain terminates here; upload to HAP.
        take_up = accept & vis_self
        upload = _tree_select(take_up, inbox, upload)
        up_mass = jnp.where(take_up, in_mass, up_mass)
        up_count = jnp.where(take_up, in_count, up_count)
        has_upload = has_upload | take_up
    # Keep the global-model echo live so XLA cannot dead-code-eliminate
    # its ppermute chain (the bytes are the point): fold an exactly-zero
    # term derived from it into up_mass.
    if cfg.ship_global_echo:
        echo_probe = sum(l.ravel()[0].astype(jnp.float32)
                         for l in jax.tree.leaves(echo))
        up_mass = up_mass + 0.0 * echo_probe
    return upload, up_mass, up_count, has_upload


def _hap_combine(contrib, cfg: FedRoundConfig, multi_pod: bool):
    """Collect per-slot contributions at the HAP tier and produce the new
    global model on every device. `contrib` is already Eq.-16-weighted."""
    if not multi_pod or not cfg.hap_ring:
        axes = ("data",) if not multi_pod else ("data", "pod")
        return _tree_psum(contrib, axes)
    # Faithful multi-pod path: per-pod HAP sum over `data`, then the
    # sink -> source chain over `pod` (§III-B3), then source -> sink
    # broadcast of the aggregate (§III-B1).
    pod_sum = _tree_psum(contrib, ("data",))
    n_pods = cfg.cmap.n_pods
    p_idx = jax.lax.axis_index("pod")
    # token passing: msg arrives at pod p carrying sum of pods > p.
    msg = jax.tree.map(jnp.zeros_like, pod_sum)
    down = hap_chain_down(n_pods) + [(0, n_pods - 1)]  # ring-closed perm
    for step in range(n_pods - 1):
        sender = n_pods - 1 - step
        add_mine = (p_idx == sender)
        msg = jax.tree.map(
            lambda m, v: jnp.where(add_mine, m + v, m), msg, pod_sum)
        msg = _tree_ppermute(msg, "pod", down)
    total = _tree_add(pod_sum, msg) if n_pods > 1 else pod_sum
    # `total` is correct at the source (pod 0); broadcast source -> sink.
    up = hap_chain_up(n_pods) + [(n_pods - 1, 0)]
    glob = jax.tree.map(
        lambda t: jnp.where(p_idx == 0, t, jnp.zeros_like(t)), total)
    for step in range(n_pods - 1):
        recv = _tree_ppermute(glob, "pod", up)
        glob = jax.tree.map(
            lambda g, r: jnp.where(p_idx == step + 1, r, g), glob, recv)
    return glob


def _round_body(w_shard, sizes_shard, visible_shard, cfg: FedRoundConfig,
                multi_pod: bool):
    """shard_map body. w_shard leaves: (1, ...) local satellite shard."""
    w = _squeeze0(w_shard)
    m_self = sizes_shard[0].astype(jnp.float32)
    vis_self = visible_shard[0]
    k = cfg.cmap.sats_per_orbit
    d_idx = jax.lax.axis_index("data")
    my_orbit = d_idx // k

    # Per-orbit data mass: gather the pod's sizes and sum my orbit's run.
    sizes_all = jax.lax.all_gather(m_self, "data")          # (D,)
    m_orbit = jax.lax.dynamic_slice(sizes_all, (my_orbit * k,), (k,)).sum()

    upload, up_mass, up_count, has_up = _ring_phase(
        w, m_self, vis_self, m_orbit, cfg)

    # ---- Eq. 16 weighting of each upload.
    n_orbits_total = cfg.cmap.n_orbits * (cfg.cmap.n_pods if multi_pod else 1)
    if cfg.orbit_weighting == "paper":
        weight = up_mass / m_orbit / n_orbits_total
    else:
        m_total = jax.lax.psum(m_self, ("data", "pod") if multi_pod
                               else ("data",))
        weight = up_mass / m_total
    weight = jnp.where(has_up, weight, 0.0)
    contrib = _tree_scale(upload, weight)

    # ---- Eq. 15 gating: every satellite covered exactly once?
    axes = ("data", "pod") if multi_pod else ("data",)
    covered = jax.lax.psum(jnp.where(has_up, up_count, 0.0), axes)
    n_sats = cfg.cmap.sats_per_pod * (cfg.cmap.n_pods if multi_pod else 1)
    gate = covered >= n_sats - 0.5

    glob = _hap_combine(contrib, cfg, multi_pod)
    # Broadcast the new global into every satellite replica; if gated,
    # keep the current replicas (aggregation rescheduled — paper Alg. 1
    # line 18).
    new_w = jax.tree.map(
        lambda g, old: jnp.where(gate, g.astype(old.dtype), old),
        glob, w)
    stats = {
        "gate": gate.astype(jnp.float32),
        "covered": covered,
        "upload_mass": jax.lax.psum(up_mass, axes),
    }
    return _expand0(new_w), stats


def _specs_for(tree, cmap: ConstellationMeshMap, multi_pod: bool,
               model_specs=None):
    """Leading satellite dim shards over pod+data; trailing dims over
    `model` per the provided per-leaf specs (or replicated)."""
    from repro.models.params import is_def
    lead = ("pod", "data") if multi_pod else ("data",)
    if model_specs is None:
        return jax.tree.map(
            lambda x: P(lead, *([None] * (len(x.shape)
                                          if is_def(x) else x.ndim))),
            tree, is_leaf=is_def)
    # PartitionSpec is a tuple subclass: stop tree traversal at P leaves.
    return jax.tree.map(
        lambda s: P(lead, *tuple(s)), model_specs,
        is_leaf=lambda x: isinstance(x, P))


def build_round(
    mesh: Mesh,
    cfg: FedRoundConfig,
    param_tree_example: Any,
    model_specs: Any = None,
    kind: str = "fedhap",
):
    """Returns a jit-able function (params_S, sizes, visible) -> (params_S,
    stats) implementing the chosen round on `mesh`.

    params_S leaves have leading dim = total satellites; `model_specs`
    optionally gives the trailing-dim PartitionSpec per leaf (tuples).
    """
    multi_pod = "pod" in mesh.axis_names
    cfg.cmap.validate_mesh(mesh)
    pspecs = _specs_for(param_tree_example, cfg.cmap, multi_pod, model_specs)
    lead = ("pod", "data") if multi_pod else ("data",)
    scalar_spec = P(lead)

    if kind == "fedavg":
        body = functools.partial(_fedavg_body, multi_pod=multi_pod)
    elif kind == "fedhap":
        body = functools.partial(_round_body, cfg=cfg, multi_pod=multi_pod)
    elif kind == "fedhap_fused":
        body = functools.partial(_fused_body, cfg=cfg, multi_pod=multi_pod)
    else:
        raise ValueError(kind)

    stats_spec = {"gate": P(), "covered": P(), "upload_mass": P()}
    if kind == "fedavg":
        stats_spec = {"gate": P(), "covered": P(), "upload_mass": P()}

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pspecs, scalar_spec, scalar_spec),
        out_specs=(pspecs, stats_spec),
        check_vma=False,
    )


def _fedavg_body(w_shard, sizes_shard, visible_shard, multi_pod: bool):
    """Star-topology FedAvg: weighted all-reduce over all satellites.

    Visibility is ignored (classical FedAvg assumes a reachable PS); kept
    in the signature for a uniform interface.
    """
    w = _squeeze0(w_shard)
    m_self = sizes_shard[0].astype(jnp.float32)
    axes = ("data", "pod") if multi_pod else ("data",)
    m_total = jax.lax.psum(m_self, axes)
    contrib = _tree_scale(w, m_self / m_total)
    glob = _tree_psum(contrib, axes)
    new_w = jax.tree.map(lambda g, old: g.astype(old.dtype), glob, w)
    stats = {
        "gate": jnp.ones(()),
        "covered": jax.lax.psum(jnp.ones(()), axes),
        "upload_mass": m_total,
    }
    return _expand0(new_w), stats


# ===================================================================
def _fused_body(w_shard, sizes_shard, visible_shard, cfg: FedRoundConfig,
                multi_pod: bool):
    """Beyond-paper fused round: closed-form per-satellite weight, single
    weighted psum. Algebraically equal to the faithful ring (see
    tests/test_fedhap_mesh).

    Per-satellite weight mu_x = (m_seg / m_l) * lam_x / L   (paper orbit
    weighting), where lam_x is the Eq.-14 chain weight of x inside its
    segment and m_seg the segment mass. All scalar bookkeeping runs on
    (D,)-sized vectors from one tiny all_gather; the chain math itself is
    the shared closed-form engine (`repro.core.weights.chain_stats`).
    """
    w = _squeeze0(w_shard)
    m_self = sizes_shard[0].astype(jnp.float32)
    vis_self = visible_shard[0]
    k = cfg.cmap.sats_per_orbit
    d_idx = jax.lax.axis_index("data")
    my_orbit = d_idx // k
    my_slot = d_idx % k

    sizes_all = jax.lax.all_gather(m_self, "data")         # (D,)
    vis_all = jax.lax.all_gather(vis_self, "data")         # (D,)
    orbit_sizes = jax.lax.dynamic_slice(sizes_all, (my_orbit * k,), (k,))
    orbit_vis = jax.lax.dynamic_slice(vis_all, (my_orbit * k,), (k,))
    m_orbit = orbit_sizes.sum()

    # Closed-form chain weight of every slot in my orbit (the static
    # ring unroll lives in the shared engine); pick out my own.
    lam_vec, seg_vec = chain_stats(orbit_vis, orbit_sizes,
                                   cfg.partial_mode, xp=jnp)
    lam = lam_vec[my_slot]
    seg_mass_full = seg_vec[my_slot]
    orbit_has_vis = orbit_vis.astype(bool).any()

    n_orbits_total = cfg.cmap.n_orbits * (cfg.cmap.n_pods if multi_pod else 1)
    axes = ("data", "pod") if multi_pod else ("data",)
    if cfg.orbit_weighting == "paper":
        mu = seg_mass_full / m_orbit * lam / n_orbits_total
    else:
        m_total = jax.lax.psum(m_self, axes)
        mu = seg_mass_full / m_total * lam

    gate = jax.lax.psum(jnp.where(orbit_has_vis, 1.0, 0.0), axes) >= (
        jax.lax.psum(jnp.ones(()), axes) - 0.5)

    # The weighted-psum tail is the shared sharded fold (identical to the
    # simulator megastep's per-shard aggregation, S_local == 1 here).
    glob = sharded_fold(w_shard, mu[None], axes)
    new_w = jax.tree.map(
        lambda g, old: jnp.where(gate, g.astype(old.dtype), old), glob, w)
    stats = {
        "gate": gate.astype(jnp.float32),
        "covered": jax.lax.psum(jnp.where(orbit_has_vis, 1.0, 0.0), axes)
        * k,
        "upload_mass": jax.lax.psum(m_self * (mu > 0), axes),
    }
    return _expand0(new_w), stats

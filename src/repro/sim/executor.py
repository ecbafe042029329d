"""Fused on-device execution for the timeline simulator.

The per-round reference path (``Strategy.step``) pays per-round Python:
``stack([params] * n_sats)`` host copies, a host mini-batch gather and
upload, one dispatch per train / fold / eval, and a blocking sync every
round. :class:`FusedExecutor` is the jitted execute phase of the
plan/execute split: strategies plan in pure numpy (contact times,
Eq. 14-16 weights, staleness discounts — no rng, no params), batch K
planned rounds into schedule tensors, and execute them as ONE donated
dispatch:

- the dataset and eval set live on device; per-round mini-batches are
  gathered *inside* the jitted program from host-sampled index tensors
  (identical rng stream to the reference path);
- the global model stays resident and is broadcast to the satellite
  replicas inside jit (:func:`repro.core.treeops.tree_broadcast` — a
  view, not ``n_sats`` host copies);
- train -> weighted fold -> eval fuse into one ``round_megastep`` whose
  fold runs through the Pallas ``fedagg`` kernel on accelerators and
  the einsum reference (:func:`repro.core.treeops.tree_combine`) on CPU
  (:func:`repro.kernels.ops.fold_stacked_tree`);
- a ``lax.scan`` chains K megasteps per dispatch (``run_block`` for the
  synchronous round family, ``cycle_block`` for the routed event
  family), returning to the host only between blocks for history
  recording and termination checks.

Accuracies come back as one stacked transfer per block; rounds the plan
marked invalid (padding) or non-eval are skipped via ``lax.cond``.

**Multi-device execution** (``mesh=``): given a mesh with a ``data``
axis (`repro.launch.mesh.make_sim_mesh` / ``make_debug_mesh``), the
megastep is ``shard_map``-ped over the satellite axis: schedule and
batch-index tensors shard their satellite dim over ``data``, the global
model and eval set stay replicated, each device trains and folds only
its own satellite shard, and the per-device partial folds meet in ONE
weighted ``psum`` — :func:`repro.core.mesh_round.sharded_fold`, the
production mesh round's own collective tail, so ``launch/`` and
``sim/`` share one aggregation code path. Satellite counts that do not
divide the device count are padded with zero-weight dead satellites
(index rows 0, weight 0.0 — exactly-zero contribution through both
fold backends), so weights and eval are unaffected. A 1-device mesh is
bit-identical to the unsharded path; at D devices the psum reduction
order differs from the single einsum by a few f32 ULPs (the documented
fedagg-vs-einsum bound of ``tests/test_sim_fused.py``).

The tick-driven fedsat/fedspace baselines keep the single-device path:
their per-tick participant sets are small, data-dependent slices where
resharding would dominate; their histories are mesh-independent by
construction.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.mesh_round import sharded_fold
from repro.core.treeops import (
    tree_broadcast,
    tree_row,
    tree_set_row,
)
from repro.kernels.ops import fold_stacked_tree


def tree_combine_many(stacked: Any, weight_rows: Any) -> Any:
    """K weighted folds of one stacked tree in a single batched einsum.

    ``weight_rows`` is ``(K, S)``; returns a tree of ``(K, ...)`` leaves
    with row k equal to ``tree_combine(stacked, weight_rows[k])``. Each
    leaf is read ONCE for all K folds — the schedule-tensor form of K
    independent planned aggregations (weight sweeps, the wallclock
    bench), as opposed to the sequential fold inside ``run_block``
    where round k+1's input depends on round k's output.
    """
    w = jnp.asarray(weight_rows, jnp.float32)
    return jax.tree.map(lambda x: jnp.einsum("ks,s...->k...", w, x), stacked)


# The event tensors of a cycle block, in the programs' argument order,
# with the dtype each goes to the device as.
_EVENT_TENSORS = (("l", np.int32), ("idx", np.int32), ("lam", np.float32),
                  ("rhos", np.float32), ("keep", np.float32),
                  ("slot", np.int32), ("flush", bool), ("do_eval", bool),
                  ("valid", bool))


class FusedExecutor:
    """Device-resident data + jitted block programs for one engine."""

    def __init__(self, trainer: Any, fd: Any, eval_images: np.ndarray,
                 eval_labels: np.ndarray, *, eval_chunk: int = 1024,
                 use_pallas: Optional[bool] = None, mesh: Any = None):
        self.trainer = trainer
        self.use_pallas = use_pallas
        self.mesh = mesh
        if mesh is not None and "data" not in mesh.axis_names:
            raise ValueError(
                f"executor mesh needs a 'data' axis to shard the "
                f"satellite dim over; got axes {mesh.axis_names}")
        self.n_shards = int(dict(mesh.shape)["data"]) if mesh is not None \
            else 1
        self._jit = {}          # (kind, *shape key) -> compiled program

        # Eval set, padded to whole chunks; pad labels are -1 so they
        # never match an argmax in [0, num_classes).
        n = len(eval_images)
        self._eval_n = n
        c = max(1, min(eval_chunk, n)) if n else 1
        pad = (-n) % c
        ex = np.asarray(eval_images)
        ey = np.asarray(eval_labels, np.int32)
        if pad:
            ex = np.concatenate(
                [ex, np.zeros((pad,) + ex.shape[1:], ex.dtype)])
            ey = np.concatenate([ey, np.full(pad, -1, ey.dtype)])
        # Training set + chunked eval set, handed to every program as an
        # argument: a closed-over array would be baked into the
        # executable as a constant (a ~250 MB program for the paper
        # dataset, too large for a persistent compile cache). The mesh
        # programs take a replicated copy; the tick programs, which run
        # on one device, the local one.
        self._data_local = (
            jnp.asarray(fd.images),
            jnp.asarray(np.asarray(fd.labels, np.int32)),
            jnp.asarray(ex.reshape(-1, c, *ex.shape[1:])),
            jnp.asarray(ey.reshape(-1, c)))
        self._data = self._replicate(self._data_local)

    # ------------------------------------------------------------ basics
    def _call(self, key: tuple, build: Any, args: tuple,
              uploads: tuple = ()) -> Any:
        """Every program call goes through here. Runs the program cached
        under ``key`` (made by ``build()`` on first use) on the device
        arguments ``args`` followed by ``uploads``, ``(host array,
        dtype)`` pairs sent to the device here (``obs.upload``). A
        program's first call, which traces and compiles it (or reads it
        from the persistent cache), is an ``exec.build`` span; every
        later one an ``exec.dispatch`` span. Either counts one
        dispatch."""
        fn = self._jit.get(key)
        name = "exec.dispatch"
        if fn is None:
            fn = self._jit[key] = build()
            name = "exec.build"
        with obs.span(name):
            out = fn(*args, *(obs.upload(x, dt) for x, dt in uploads))
        obs.count("exec.dispatches")
        return out

    def _fold(self, stacked: Any, weights: Any) -> Any:
        with obs.scope("fold"):
            return fold_stacked_tree(stacked, weights, self.use_pallas)

    def _sharded_fold(self, stacked: Any, weights: Any) -> Any:
        with obs.scope("fold"):
            return sharded_fold(stacked, weights, ("data",),
                                self.use_pallas)

    def _replicate(self, tree: Any) -> Any:
        """Commit a param tree replicated over the mesh (no-op without
        one) so donated block inputs land pre-sharded."""
        if self.mesh is None:
            return tree
        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    @staticmethod
    def _pad_sat_axis(arrs: dict, names, axis: int, multiple: int) -> dict:
        """Pad each named tensor's satellite ``axis`` up to a multiple of
        the shard count with dead satellites: index tensors get row-0
        indices (finite training input), weight tensors get 0.0 (their
        fold contribution is exactly zero — ``kernels.ops
        .pad_stacked_rows`` is the device-side statement of the same
        contract)."""
        out = dict(arrs)
        for name in names:
            a = out[name]
            pad = (-a.shape[axis]) % multiple
            if pad:
                width = [(0, 0)] * a.ndim
                width[axis] = (0, pad)
                out[name] = np.pad(a, width)   # zero rows / zero weights
        return out

    def _device_acc(self, data: tuple, params: Any) -> jax.Array:
        """Fraction of the eval set classified correctly — the chunked
        accuracy reduction run inside the megastep (single f32 scalar;
        no host transfer until the block boundary)."""
        if self._eval_n == 0:
            return jnp.float32(0.0)
        model = self.trainer.model
        ex, ey = data[2], data[3]

        def chunk_correct(xy):
            x, y = xy
            pred = jnp.argmax(model.forward(params, x), axis=-1)
            return jnp.sum((pred == y).astype(jnp.float32))

        with obs.scope("eval"):
            correct = jnp.sum(jax.lax.map(chunk_correct, (ex, ey)))
            return correct / jnp.float32(self._eval_n)

    def _nan_acc(self, params: Any) -> jax.Array:
        return jnp.full((), jnp.nan, jnp.float32)

    def _batches(self, data: tuple, idx: jax.Array, n_rep: int,
                 n_steps: int):
        """Device gather of ``n_rep`` replicas' sampled mini-batches."""
        bs = self.trainer.batch_size
        x_all, y_all = data[0], data[1]
        x = x_all[idx].reshape(n_rep, n_steps, bs, *x_all.shape[1:])
        return x, y_all[idx].reshape(n_rep, n_steps, bs)

    def _train(self, data: tuple, base: Any, idx: jax.Array, n_rep: int,
               n_steps: int) -> Any:
        """The megastep's train half: device gather of the sampled
        mini-batch indices + one SGD burst over ``n_rep`` replicas
        broadcast from ``base`` inside jit
        (``LocalTrainer.multi_step_many``: for the CNN the replica axis
        lives in the activations' channels, for the MLP in vmap's
        leading axis)."""
        with obs.scope("train"):
            x, y = self._batches(data, idx, n_rep, n_steps)
            trained, _ = self.trainer.multi_step_many(
                tree_broadcast(base, n_rep), x, y)
        return trained

    def broadcast_rows(self, params: Any, n: int) -> Any:
        """Materialized (n, ...) stacked copies of ``params`` on device
        (per-orbit / per-satellite base-model tables)."""
        return self._call(
            ("bcast", n),
            lambda: jax.jit(lambda p: jax.tree.map(
                lambda x: jnp.tile(x[None], (n,) + (1,) * x.ndim), p)),
            (params,))

    def zero_rows(self, params: Any, n: int) -> Any:
        """(n, ...) zero-filled stacked tree matching ``params`` leaves,
        built inside jit (an eager ``jnp.zeros`` is a host->device
        scalar transfer, which the sanitizer's transfer guard rejects
        in the block loop)."""
        return self._call(
            ("zeros", n),
            lambda: jax.jit(lambda p: jax.tree.map(
                lambda x: jnp.zeros((n,) + x.shape, x.dtype), p)),
            (params,))

    # -------------------------------------------- synchronous round family
    def run_block(self, params: Any, idx: np.ndarray, mu: np.ndarray,
                  do_eval: np.ndarray, valid: np.ndarray):
        """Execute K planned rounds in one donated dispatch.

        ``idx``: (K, S, n_steps*bs) sampled dataset indices; ``mu``:
        (K, S) planned global weights; ``do_eval``/``valid``: (K,)
        flags. Returns ``(params, accs)`` — the device-resident global
        after the last valid round and a (K,) host array of accuracies
        (NaN where not evaluated): ONE transfer per block.

        Fault degradation rides this contract with no extra code path:
        a round that lost every upload arrives as ``valid=False`` (the
        ``lax.cond`` carries params through unchanged) and a partially
        lost round arrives with the lost satellites' ``mu`` rows
        renormalized to zero — zero-weight rows drop out of the fold
        einsum exactly like padding rows.

        With a mesh, dispatches to the satellite-sharded program (same
        plan tensors, same return contract).
        """
        if self.mesh is not None:
            return self._run_block_sharded(params, idx, mu, do_eval,
                                           valid)
        K, S, need = idx.shape
        n_steps = need // self.trainer.batch_size

        def block(params, data, idx, mu, do_eval, valid):
            def body(p, inp):
                idx_r, mu_r, ev, va = inp

                def megastep(p):
                    trained = self._train(data, p, idx_r, S, n_steps)
                    return self._fold(trained, mu_r)

                p = jax.lax.cond(va, megastep, lambda q: q, p)
                acc = jax.lax.cond(
                    ev & va, functools.partial(self._device_acc, data),
                    self._nan_acc, p)
                return p, acc

            return jax.lax.scan(body, params, (idx, mu, do_eval, valid))

        params, accs = self._call(
            ("round", K, S, n_steps),
            lambda: jax.jit(block, donate_argnums=0),
            (params, self._data),
            ((idx, np.int32), (mu, np.float32), (do_eval, bool),
             (valid, bool)))
        return params, obs.fetch(accs)

    def _run_block_sharded(self, params: Any, idx: np.ndarray,
                           mu: np.ndarray, do_eval: np.ndarray,
                           valid: np.ndarray):
        """The mesh round AS the simulator's training step: ``run_block``
        shard_map-ped over the satellite axis.

        ``idx``/``mu`` shard their satellite dim over ``data`` (padded to
        a multiple of the device count with zero-index/zero-weight dead
        satellites); params and the eval set stay replicated. Each device
        trains its own ``S/D`` replicas, then the per-device partial
        folds meet in :func:`repro.core.mesh_round.sharded_fold` — the
        production round's collective tail, ONE weighted psum per round.
        The eval reduction runs replicated on the psum'd global (every
        device computes the identical scalar), so accuracies keep the
        single-transfer-per-block contract.
        """
        D = self.n_shards
        padded = self._pad_sat_axis(
            {"idx": idx, "mu": mu}, ("idx", "mu"), 1, D)
        idx, mu = padded["idx"], padded["mu"]
        K, Sp, need = idx.shape
        s_loc = Sp // D
        n_steps = need // self.trainer.batch_size

        def block(params, data, idx, mu, do_eval, valid):
            def body(p, inp):
                idx_r, mu_r, ev, va = inp

                def megastep(p):
                    trained = self._train(data, p, idx_r, s_loc, n_steps)
                    return self._sharded_fold(trained, mu_r)

                p = jax.lax.cond(va, megastep, lambda q: q, p)
                acc = jax.lax.cond(
                    ev & va, functools.partial(self._device_acc, data),
                    self._nan_acc, p)
                return p, acc

            return jax.lax.scan(body, params, (idx, mu, do_eval, valid))

        sharded = jax.shard_map(
            block, mesh=self.mesh,
            in_specs=(P(), P(), P(None, "data", None), P(None, "data"),
                      P(), P()),
            out_specs=(P(), P()), check_vma=False)
        params, accs = self._call(
            ("round_sharded", K, Sp, n_steps),
            lambda: jax.jit(sharded, donate_argnums=0),
            (self._replicate(params), self._data),
            ((idx, np.int32), (mu, np.float32), (do_eval, bool),
             (valid, bool)))
        return params, obs.fetch(accs)

    def fold_block(self, stacked: Any, weight_rows: np.ndarray) -> Any:
        """K planned folds of a fixed stacked tree as one dispatch (the
        schedule-tensor batched aggregation; see tree_combine_many)."""
        return self._call(("fold_block",),
                          lambda: jax.jit(tree_combine_many), (stacked,),
                          ((weight_rows, np.float32),))

    # ------------------------------------------------- routed event family
    def cycle_block(self, params: Any, bases: Any, buf: Any,
                    ev: dict[str, np.ndarray],
                    sat_axes: tuple = ("idx", "lam")):
        """Execute K planned cycle events in one donated dispatch.

        Carries ``(global, per-orbit cycle bases, staleness buffer)``
        through a ``lax.scan``; each event trains orbit ``l``'s members
        from the base the cycle launched against, folds them along the
        planned Eq.-14 chain weights, writes the orbit model into its
        buffer slot, and — on flush events — applies the planned
        staleness-discounted fold ``keep*g + rhos @ buffer``. Event
        tensors (all leading dim K): ``l`` int, ``idx`` (K, k, need),
        ``lam`` (K, k), ``rhos`` (K, B), ``keep``, ``slot`` int,
        ``flush``, ``do_eval``, ``valid``. Returns
        ``(params, bases, buf, accs)`` with accs transferred once.

        With a mesh, dispatches to the member-sharded program;
        ``sat_axes`` names the tensors whose axis 1 is the satellite
        (cycle-member) dim to shard over ``data``.
        """
        if self.mesh is not None:
            return self._cycle_block_sharded(params, bases, buf, ev,
                                             sat_axes)
        K, k, need = ev["idx"].shape
        B = ev["rhos"].shape[1]
        n_steps = need // self.trainer.batch_size

        def block(params, bases, buf, data, l, idx, lam, rhos, keep,
                  slot, flush, do_eval, valid):
            def body(carry, inp):
                g, bases, buf = carry
                (l_e, idx_e, lam_e, rhos_e, keep_e, slot_e, fl, evf,
                 va) = inp

                def event(args):
                    g, bases, buf = args
                    base = tree_row(bases, l_e)
                    trained = self._train(data, base, idx_e, k,
                                          n_steps)
                    orbit_model = self._fold(trained, lam_e)
                    buf = tree_set_row(buf, slot_e, orbit_model)

                    def do_flush(g):
                        return jax.tree.map(
                            lambda gg, bb: keep_e * gg + jnp.einsum(
                                "s,s...->...", rhos_e, bb),
                            g, buf)

                    g = jax.lax.cond(fl, do_flush, lambda q: q, g)
                    bases = tree_set_row(bases, l_e, g)
                    return g, bases, buf

                g, bases, buf = jax.lax.cond(
                    va, event, lambda a: a, (g, bases, buf))
                acc = jax.lax.cond(
                    evf & va, functools.partial(self._device_acc, data),
                    self._nan_acc, g)
                return (g, bases, buf), acc

            (g, bases, buf), accs = jax.lax.scan(
                body, (params, bases, buf),
                (l, idx, lam, rhos, keep, slot, flush, do_eval,
                 valid))
            return g, bases, buf, accs

        g, bases, buf, accs = self._call(
            ("cycle", K, k, B, n_steps),
            lambda: jax.jit(block, donate_argnums=(0, 1, 2)),
            (params, bases, buf, self._data),
            tuple((ev[name], dt) for name, dt in _EVENT_TENSORS))
        return g, bases, buf, obs.fetch(accs)

    def _cycle_block_sharded(self, params: Any, bases: Any, buf: Any,
                             ev: dict[str, np.ndarray], sat_axes: tuple):
        """``cycle_block`` shard_map-ped over the cycle-member axis.

        Per-event member tensors (``idx``, ``lam``) shard axis 1 over
        ``data`` (padded with zero-index/zero-weight dead members);
        the global, the per-orbit base table, and the staleness buffer
        stay replicated — the per-member fold meets in
        :func:`repro.core.mesh_round.sharded_fold`'s psum, after which
        buffer writes and flush arithmetic run replicated (identical on
        every device, no collective).
        """
        D = self.n_shards
        ev = self._pad_sat_axis(ev, sat_axes, 1, D)
        K, kp, need = ev["idx"].shape
        k_loc = kp // D
        B = ev["rhos"].shape[1]
        n_steps = need // self.trainer.batch_size

        def block(params, bases, buf, data, l, idx, lam, rhos, keep,
                  slot, flush, do_eval, valid):
            def body(carry, inp):
                g, bases, buf = carry
                (l_e, idx_e, lam_e, rhos_e, keep_e, slot_e, fl, evf,
                 va) = inp

                def event(args):
                    g, bases, buf = args
                    base = tree_row(bases, l_e)
                    trained = self._train(data, base, idx_e, k_loc,
                                          n_steps)
                    orbit_model = self._sharded_fold(trained, lam_e)
                    buf = tree_set_row(buf, slot_e, orbit_model)

                    def do_flush(g):
                        return jax.tree.map(
                            lambda gg, bb: keep_e * gg + jnp.einsum(
                                "s,s...->...", rhos_e, bb),
                            g, buf)

                    g = jax.lax.cond(fl, do_flush, lambda q: q, g)
                    bases = tree_set_row(bases, l_e, g)
                    return g, bases, buf

                g, bases, buf = jax.lax.cond(
                    va, event, lambda a: a, (g, bases, buf))
                acc = jax.lax.cond(
                    evf & va, functools.partial(self._device_acc, data),
                    self._nan_acc, g)
                return (g, bases, buf), acc

            (g, bases, buf), accs = jax.lax.scan(
                body, (params, bases, buf),
                (l, idx, lam, rhos, keep, slot, flush, do_eval,
                 valid))
            return g, bases, buf, accs

        sharded = jax.shard_map(
            block, mesh=self.mesh,
            in_specs=(P(), P(), P(), P(), P(), P(None, "data", None),
                      P(None, "data"), P(), P(), P(), P(), P(), P()),
            out_specs=(P(), P(), P(), P()), check_vma=False)
        g, bases, buf, accs = self._call(
            ("cycle_sharded", K, kp, B, n_steps),
            lambda: jax.jit(sharded, donate_argnums=(0, 1, 2)),
            (self._replicate(params), self._replicate(bases),
             self._replicate(buf), self._data),
            tuple((ev[name], dt) for name, dt in _EVENT_TENSORS))
        return g, bases, buf, obs.fetch(accs)

    def cycle_fold_block(self, params: Any, buf: Any, stacked_k: Any,
                         ev: dict[str, np.ndarray]):
        """Scheduling-bench variant of :meth:`cycle_block`: identical
        per-event fold/buffer/flush arithmetic, but the orbit model
        folds a FIXED stacked member tree instead of freshly trained
        replicas (local SGD excluded, as in ``benchmarks.sim_wallclock``).
        Returns ``(params, buf)``; no eval."""
        K = len(ev["l"])
        B = ev["rhos"].shape[1]

        def block(params, buf, stacked_k, lam, rhos, keep, slot, flush,
                  valid):
            def body(carry, inp):
                g, buf = carry
                lam_e, rhos_e, keep_e, slot_e, fl, va = inp

                def event(args):
                    g, buf = args
                    orbit_model = self._fold(stacked_k, lam_e)
                    buf = tree_set_row(buf, slot_e, orbit_model)

                    def do_flush(g):
                        return jax.tree.map(
                            lambda gg, bb: keep_e * gg + jnp.einsum(
                                "s,s...->...", rhos_e, bb),
                            g, buf)

                    g = jax.lax.cond(fl, do_flush, lambda q: q, g)
                    return g, buf

                g, buf = jax.lax.cond(va, event, lambda a: a, (g, buf))
                return (g, buf), None

            (g, buf), _ = jax.lax.scan(
                body, (params, buf), (lam, rhos, keep, slot, flush, valid))
            return g, buf

        # No donation: the wallclock benches re-drive from the same
        # initial params when timing warm vs steady-state.
        return self._call(
            ("cycle_fold", K, B), lambda: jax.jit(block),
            (params, buf, stacked_k),
            ((ev["lam"], np.float32), (ev["rhos"], np.float32),
             (ev["keep"], np.float32), (ev["slot"], np.int32),
             (ev["flush"], bool), (ev["valid"], bool)))

    # ------------------------------------------- tick-driven baselines
    #
    # fedsat/fedspace participant counts vary tick to tick (visited
    # orbits, rising-edge passes), so event shapes are padded up to the
    # next power of two before dispatch: the jit cache holds O(log S)
    # programs instead of one per distinct count. Padding rows duplicate
    # row 0 (same value on scatter, zero weight on folds) and carry a
    # validity mask where a duplicate write would be wrong.

    @staticmethod
    def _pow2(n: int) -> int:
        return 1 << max(0, int(np.ceil(np.log2(max(1, n)))))

    def fedsat_event(self, params: Any, bases: Any, visited: np.ndarray,
                     idx: np.ndarray, lam_rows: np.ndarray,
                     rhos: np.ndarray):
        """One fused fedsat tick: train every member of every visited
        orbit from its orbit's base in a single SGD burst, then the
        method's sequential per-orbit async folds — one dispatch, no
        host tree-stacking. Returns ``(params, bases)`` on device."""
        V = len(visited)
        k = lam_rows.shape[1]
        need = idx.shape[1]
        n_steps = need // self.trainer.batch_size
        Vp = self._pow2(V)
        if Vp > V:
            pad = Vp - V
            visited = np.concatenate([visited,
                                      np.repeat(visited[:1], pad)])
            idx = np.concatenate([idx, np.tile(idx[:k], (pad, 1))])
            lam_rows = np.concatenate([lam_rows,
                                       np.zeros((pad, k))])
            rhos = np.concatenate([rhos, np.zeros(pad)])
        valid = np.arange(Vp) < V

        def event(params, bases, data, visited, idx, lam_rows, rhos, valid):
            with obs.scope("train"):
                base_rows = jax.tree.map(lambda b: b[visited], bases)
                rep = jax.tree.map(
                    lambda b: jnp.repeat(b, k, axis=0), base_rows)
                x, y = self._batches(data, idx, Vp * k, n_steps)
                trained, _ = self.trainer.multi_step_many(rep, x, y)

            def orbit_fold(carry, j):
                g, bases = carry
                rows = jax.tree.map(
                    lambda t: jax.lax.dynamic_slice_in_dim(t, j * k, k),
                    trained)
                orbit_model = self._fold(rows, lam_rows[j])
                rho = jnp.where(valid[j], rhos[j], 0.0)
                g = jax.tree.map(
                    lambda gg, oo: (1.0 - rho) * gg + rho * oo,
                    g, orbit_model)
                bases = jax.lax.cond(
                    valid[j],
                    lambda a: tree_set_row(a[0], visited[j], a[1]),
                    lambda a: a[0], (bases, g))
                return (g, bases), None

            with obs.scope("fold"):
                (g, bases), _ = jax.lax.scan(
                    orbit_fold, (params, bases), jnp.arange(Vp))
            return g, bases

        return self._call(
            ("fedsat", Vp, k, n_steps),
            lambda: jax.jit(event, donate_argnums=(0, 1)),
            (params, bases, self._data_local),
            ((visited, np.int32), (idx, np.int32), (lam_rows, np.float32),
             (rhos, np.float32), (valid, bool)))

    def fedspace_train(self, params: Any, bases: Any, sats: np.ndarray,
                       idx: np.ndarray):
        """One fused fedspace pass burst: train ``sats`` from their
        per-satellite bases, return the stacked deltas (padded rows
        past ``len(sats)`` are duplicates to be zero-weighted at
        flush), and reset those base rows to the current global — one
        dispatch. Returns ``(deltas, bases)``."""
        N = len(sats)
        need = idx.shape[1]
        n_steps = need // self.trainer.batch_size
        Np = self._pow2(N)
        if Np > N:
            pad = Np - N
            # duplicate row 0: the base scatter rewrites sats[0] with
            # the same value; the delta rows get weight 0 at flush.
            sats = np.concatenate([sats, np.repeat(sats[:1], pad)])
            idx = np.concatenate([idx, np.tile(idx[:1], (pad, 1))])

        def event(params, bases, data, sats, idx):
            rows = jax.tree.map(lambda b: b[sats], bases)
            with obs.scope("train"):
                x, y = self._batches(data, idx, Np, n_steps)
                trained, _ = self.trainer.multi_step_many(rows, x, y)
            deltas = jax.tree.map(lambda t, r: t - r, trained, rows)
            bases = jax.tree.map(
                lambda b, p: b.at[sats].set(
                    jnp.broadcast_to(p[None], (Np,) + p.shape)),
                bases, params)
            return deltas, bases

        return self._call(
            ("fedspace", Np, n_steps),
            lambda: jax.jit(event, donate_argnums=1),
            (params, bases, self._data_local),
            ((sats, np.int32), (idx, np.int32)))

    def fedspace_flush(self, params: Any, stacked_deltas: Any,
                       wts: np.ndarray):
        """Buffered flush: ``params + Σ_j wts[j]·delta_j`` fused on
        device (the fold through the shared aggregation backend).
        Inputs are padded to the next power-of-two row count (zero
        weights, zero rows) so the jit cache stays O(log B)."""
        B = len(wts)
        Bp = self._pow2(B)
        if Bp > B:
            pad = Bp - B
            wts = np.concatenate([wts, np.zeros(pad)])
            # Zero-padding happens inside jit: eager jnp.zeros (and
            # even an eager x[0] slice) is a host->device transfer,
            # which the sanitizer's guard rejects in the block loop.
            # Pad programs are keyed per (B, Bp) but trivial; the
            # expensive fold below stays O(log B) compiles.
            stacked_deltas = self._call(
                ("pad_rows", B, Bp),
                lambda: jax.jit(lambda t: jax.tree.map(
                    lambda x: jnp.concatenate(
                        [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)]),
                    t)),
                (stacked_deltas,))

        def flush(params, stacked, wts):
            upd = self._fold(stacked, wts)
            return jax.tree.map(lambda p, u: p + u, params, upd)

        return self._call(
            ("fedspace_flush", Bp),
            lambda: jax.jit(flush, donate_argnums=0),
            (params, stacked_deltas), ((wts, np.float32),))


__all__ = ["FusedExecutor", "tree_combine_many"]

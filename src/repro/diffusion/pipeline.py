"""DiT serving pipeline: the model-executor side of the adapter (§5.2).

Holds real (reduced-size) JAX weights for the text encoder, DiT denoiser,
and VAE decoder, and executes trajectory tasks per-rank with GFC
collectives inside (sequence-parallel denoising).  Used by the thread
backend for faithful distributed-semantics runs; the simulator uses only
the cost model.
"""
from __future__ import annotations

import contextlib
import hashlib
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.executor import rank_device
from repro.core.gfc import GroupDescriptor, GroupFreeComm
from repro.core.trajectory import (ExecutionLayout, RequestGraph,
                                   TrajectoryTask)
from repro.diffusion import schedule
from repro.diffusion.adapters import field_view
from repro.diffusion.feature_cache import snapshot_kv
from repro.kernels import ops
from repro.models import dit, text_encoder, vae
from repro.models.layers import split_params


_NO_REGION = contextlib.nullcontext()


def _req_seed(request_id: str) -> int:
    return int(hashlib.sha1(request_id.encode()).hexdigest()[:8], 16)


class DiTPipeline:
    """Executable DiT pipeline: the configured denoiser plus a toy text
    encoder and VAE decoder, with seeded random weights."""

    def __init__(self, cfg: ModelConfig, seed: int = 0):
        assert cfg.family == "dit"
        self.cfg = cfg
        key = jax.random.PRNGKey(seed)
        ks = jax.random.split(key, 3)
        self.dit_params, _ = split_params(dit.init(ks[0], cfg))
        self.txt_cfg = text_encoder.encoder_config(
            cfg.dit.cond_dim, vocab=512).reduced(
            d_model=cfg.dit.cond_dim, num_heads=4, num_kv_heads=4,
            head_dim=cfg.dit.cond_dim // 4, d_ff=cfg.dit.cond_dim * 2)
        self.txt_params, _ = split_params(
            text_encoder.init(ks[1], self.txt_cfg))
        self.vae_params, _ = split_params(vae.init(ks[2], cfg, hidden=32))
        self._placed: dict = {}
        self._place_lock = threading.Lock()
        # telemetry plane (DESIGN.md §15): set by the serving engine; the
        # step's phases then run as gfdit.step.* regions
        self.telemetry = None

    def _region(self, name: str, **stats):
        tel = self.telemetry
        return _NO_REGION if tel is None else tel.region(name, **stats)

    def weights(self, rank: int):
        """(dit, text, vae) parameter trees on ``rank``'s device, copied
        there once, on first use — so the weights must not change once
        serving has started."""
        dev = rank_device(rank)
        with self._place_lock:
            if dev not in self._placed:
                self._placed[dev] = jax.device_put(
                    (self.dit_params, self.txt_params, self.vae_params), dev)
            return self._placed[dev]

    def _forward(self, rank: int, x, t, txt, off: int, n_total: int,
                 kv_gather, *, guidance=None, grids=None):
        """The denoiser's forward on ``rank``, as the step's
        ``gfdit.step.forward`` region.  The region carries the model's
        layer count (and, for more than one block kind, each kind's), and
        its ``builds`` stat counts the layer programs the process built
        while it ran (``dit.builds``): 0 once every shape is warm."""
        segs = dit.segments(self.cfg)
        counts = dict(segs) if len(segs) > 1 else {}
        with self._region("gfdit.step.forward",
                          layers=sum(n for _, n in segs), **counts) as late:
            before = dit.builds()
            v = dit.forward_sp_tokens(
                self.weights(rank)[0], x, t, txt, self.cfg, pos_offset=off,
                n_total=n_total, kv_gather=kv_gather, guidance=guidance,
                grids=grids)
            if late is not None:
                late["builds"] = dit.builds() - before
        return v

    def _sigmas(self, steps: int):
        return schedule.flow_sigmas(steps, self.cfg.dit.flow_shift)

    def _scales(self, reqs):
        """Each row's guidance scale, where the model takes it as an input
        (None otherwise); an unguided request runs at scale 1."""
        if not self.cfg.dit.guidance_embeds:
            return None
        return jnp.array([1.0 if r.guidance is None else r.guidance
                          for r in reqs], jnp.float32)

    def _grid(self, task):
        """(frames, rows, cols) of a denoise task's latent patches."""
        f, h, w, _ = task.meta["latent_shape"]
        p = self.cfg.dit.patch_size
        return (f, h // p, w // p)

    # ------------------------------------------------------------------
    # adapter interface: execute this rank's share of a trajectory task
    # ------------------------------------------------------------------
    def execute(self, task: TrajectoryTask, layout: ExecutionLayout,
                rank: int, comm: GroupFreeComm, graph: RequestGraph,
                desc: GroupDescriptor):
        if task.kind == "encode":
            if rank == layout.ranks[0]:
                self._encode(task, layout, rank, graph)
        elif task.kind == "denoise":
            self._denoise(task, layout, rank, comm, graph, desc)
        elif task.kind == "decode":
            if rank == layout.ranks[0]:
                self._decode(task, layout, rank, graph)
        else:
            raise ValueError(task.kind)

    # ------------------------------------------------------------------
    def execute_packed(self, members, layout: ExecutionLayout, rank: int,
                       comm: GroupFreeComm, desc: GroupDescriptor):
        """Step packing (DESIGN.md §9): run this rank's share of N
        batch-compatible denoise tasks as ONE batched forward.

        Latent shards are stacked along the batch axis (the control plane
        guarantees identical token shapes), per-member sigmas ride the
        batched timestep vector, and the SP KV all-gather runs ONCE over
        the stacked tensors — one set of GFC collectives amortized over
        the pack.  Each member's Euler update then uses its own sigma
        pair, and outputs land in per-request artifacts (no cross-request
        state is shared beyond the stacked forward)."""
        xs, txts, t_steps, sig_pairs = [], [], [], []
        for task, graph in members:
            req = graph.request
            txts.append(graph.artifacts[task.inputs[0]].data[rank]["embeds"])
            xs.append(graph.artifacts[task.inputs[1]].data[rank]["latent"])
            sigmas = self._sigmas(req.steps)
            step = task.meta["step"]
            s_now = float(sigmas[step])
            s_next = (float(sigmas[step + 1]) if step + 1 < req.steps
                      else 0.0)
            sig_pairs.append((s_now, s_next))
            t_steps.append(schedule.timestep_of_sigma(s_now))

        task0, graph0 = members[0]
        spec = graph0.artifacts[task0.inputs[1]].fields["latent"]
        view = field_view(spec, layout)
        off, size = view.slices[rank]
        n_total = spec.global_shape[0]

        stamp = task0.meta.get("cache")
        if layout.degree == 1:
            def kv_gather(k, v, layer):
                return k, v
        elif stamp is None:
            def kv_gather(k, v, layer):
                K = comm.all_gather(desc, rank, np.asarray(k), axis=1)
                V = comm.all_gather(desc, rank, np.asarray(v), axis=1)
                return jnp.asarray(K), jnp.asarray(V)
        else:
            # cross-step feature cache (DESIGN.md §11): the pack shares
            # ONE plane-stamped decision; per-member snapshots live in
            # each member's kv_cache artifact, batch rows map to members
            stores = [g.artifacts[tk.meta["cache"]["art"]].data[rank]
                      for tk, g in members]
            if stamp["mode"] == "refresh":
                def kv_gather(k, v, layer):
                    K = comm.all_gather(desc, rank, np.asarray(k), axis=1)
                    V = comm.all_gather(desc, rank, np.asarray(v), axis=1)
                    for j, store in enumerate(stores):
                        store[f"k{layer}"] = K[j]
                        store[f"v{layer}"] = V[j]
                    return jnp.asarray(K), jnp.asarray(V)
            elif ops.use_pallas_enabled(self.cfg.use_pallas):
                # fast path: hand the stale snapshot + fresh shard to
                # the fused splice kernel — no materialized concat
                def kv_gather(k, v, layer):
                    K, V = snapshot_kv(stores, layer)
                    return ops.SplicedKV(jnp.asarray(K), jnp.asarray(V),
                                         k, v, int(off))
            else:
                def kv_gather(k, v, layer):
                    K, V = snapshot_kv(stores, layer)
                    K[:, off:off + size] = np.asarray(k)
                    V[:, off:off + size] = np.asarray(v)
                    return jnp.asarray(K), jnp.asarray(V)

        with self._region("gfdit.step.inputs"):
            x = jnp.stack([jnp.asarray(s) for s in xs])    # (B, N_loc, pd)
            txt = jnp.stack([jnp.asarray(s) for s in txts])  # (B, Lt, cond)
            t = jnp.array(t_steps, jnp.float32)
        v = self._forward(rank, x, t, txt, off, n_total, kv_gather,
                          guidance=self._scales([g.request
                                                 for _, g in members]),
                          grids=tuple(self._grid(tk) for tk, _ in members))
        with self._region("gfdit.step.update"):
            new = [schedule.flow_step(x[i], v[i], s_now, s_next)
                   for i, (s_now, s_next) in enumerate(sig_pairs)]
        with self._region("gfdit.step.fetch",
                          bytes=sum(n.nbytes for n in new)):
            for (task, graph), new_x, (_, s_next) in zip(members, new,
                                                         sig_pairs):
                out_art = graph.artifacts[task.outputs[0]]
                out_art.data[rank]["latent"] = np.asarray(new_x)
                out_art.data[rank]["sigma"] = np.float32(s_next)

    # ------------------------------------------------------------------
    def _encode(self, task, layout, rank, graph):
        txt_params = self.weights(rank)[1]
        req = graph.request
        seed = _req_seed(req.id)
        key = jax.random.PRNGKey(seed)
        # synthetic prompt tokens derived from the request id (the
        # config's prompt length, the converter's declared field shape)
        toks = jax.random.randint(key, (1, self.cfg.dit.text_len), 0,
                                  self.txt_cfg.vocab_size)
        embeds = text_encoder.encode(txt_params, toks, self.txt_cfg,
                                     dtype=jnp.float32)[0]     # (Lt, cond)
        txt_art = graph.artifacts[task.outputs[0]]
        # replicated field: every rank of this layout holds a copy (a
        # same-layout successor consumes without migration)
        for r in layout.ranks:
            txt_art.data[r]["embeds"] = np.asarray(embeds)
        if req.cfg_branches == 2:
            # classifier-free guidance (DESIGN.md §14): the uncond branch
            # conditions on the null prompt (all-zero tokens)
            toks_u = jnp.zeros_like(toks)
            emb_u = text_encoder.encode(txt_params, toks_u,
                                        self.txt_cfg,
                                        dtype=jnp.float32)[0]
            for r in layout.ranks:
                txt_art.data[r]["embeds_uncond"] = np.asarray(emb_u)

        # initial noisy latent (latent preparation is part of encode stage)
        lat_art = graph.artifacts[task.outputs[1]]
        n_tok, patch_dim = lat_art.fields["latent"].global_shape
        noise = jax.random.normal(jax.random.fold_in(key, 1),
                                  (n_tok, patch_dim), jnp.float32)
        sigmas = self._sigmas(req.steps)
        full = np.asarray(noise) * sigmas[0]
        view = field_view(lat_art.fields["latent"], layout)
        for r in layout.ranks:
            off, size = view.slices[r]
            lat_art.data[r]["latent"] = full[off:off + size]
            lat_art.data[r]["sigma"] = np.float32(sigmas[0])

    # ------------------------------------------------------------------
    def _denoise(self, task, layout, rank, comm, graph, desc):
        req = graph.request
        if req.cfg_branches == 2:
            return self._denoise_guided(task, layout, rank, comm, graph,
                                        desc)
        txt_art = graph.artifacts[task.inputs[0]]
        lat_art = graph.artifacts[task.inputs[1]]
        out_art = graph.artifacts[task.outputs[0]]
        txt = txt_art.data[rank]["embeds"]
        x_shard = lat_art.data[rank]["latent"]                 # (N_loc, pd)
        spec = lat_art.fields["latent"]
        view = field_view(spec, layout)
        off, size = view.slices[rank]
        n_total = spec.global_shape[0]

        sigmas = self._sigmas(req.steps)
        step = task.meta["step"]
        sigma_now = float(sigmas[step])
        sigma_next = float(sigmas[step + 1]) if step + 1 < req.steps else 0.0

        stamp = task.meta.get("cache")
        if layout.degree == 1:
            def kv_gather(k, v, layer):
                return k, v
        elif stamp is None:
            def kv_gather(k, v, layer):
                K = comm.all_gather(desc, rank, np.asarray(k), axis=1)
                V = comm.all_gather(desc, rank, np.asarray(v), axis=1)
                return jnp.asarray(K), jnp.asarray(V)
        elif stamp["mode"] == "refresh":
            # full gather; snapshot this rank's copy per layer — every
            # rank stores the SAME gathered bytes (replicated fields),
            # and the returned arrays are exactly the uncached ones, so
            # a refresh step is bit-exact with the non-cached path
            store = graph.artifacts[stamp["art"]].data[rank]

            def kv_gather(k, v, layer):
                K = comm.all_gather(desc, rank, np.asarray(k), axis=1)
                V = comm.all_gather(desc, rank, np.asarray(v), axis=1)
                store[f"k{layer}"] = K[0]
                store[f"v{layer}"] = V[0]
                return jnp.asarray(K), jnp.asarray(V)
        elif ops.use_pallas_enabled(self.cfg.use_pallas):
            # cache hit on the Pallas fast path: the stale snapshot and
            # the fresh local shard go to the fused splice kernel, which
            # patches the K/V stream in-register (DESIGN.md §12) — no
            # collective AND no materialized concat
            store = graph.artifacts[stamp["art"]].data[rank]

            def kv_gather(k, v, layer):
                K, V = snapshot_kv([store], layer)
                return ops.SplicedKV(jnp.asarray(K), jnp.asarray(V),
                                     k, v, int(off))
        else:
            # cache hit: stale remote shards from the last refresh, with
            # THIS step's fresh local K/V spliced in — no collective
            store = graph.artifacts[stamp["art"]].data[rank]

            def kv_gather(k, v, layer):
                K, V = snapshot_kv([store], layer)
                K[:, off:off + size] = np.asarray(k)
                V[:, off:off + size] = np.asarray(v)
                return jnp.asarray(K), jnp.asarray(V)

        with self._region("gfdit.step.inputs"):
            x = jnp.asarray(x_shard)
            txt = jnp.asarray(txt)
            t = jnp.array([schedule.timestep_of_sigma(sigma_now)],
                          jnp.float32)
        v_shard = self._forward(rank, x[None], t, txt[None], off, n_total,
                                kv_gather, guidance=self._scales([req]),
                                grids=(self._grid(task),))[0]
        with self._region("gfdit.step.update"):
            new_x = schedule.flow_step(x, v_shard, sigma_now, sigma_next)
        with self._region("gfdit.step.fetch", bytes=new_x.nbytes):
            out_art.data[rank]["latent"] = np.asarray(new_x)
        out_art.data[rank]["sigma"] = np.float32(sigma_next)

    # ------------------------------------------------------------------
    def _denoise_guided(self, task, layout, rank, comm, graph, desc):
        """Classifier-free guidance denoise (DESIGN.md §14).

        ``cfg == 1``: ONE batched forward with rows [cond, uncond] on the
        whole group (the historic single-group batched-CFG path).
        ``cfg >= 2``: this rank's branch runs its row B=1 with SP
        collectives confined to the branch descriptor, then ONE merge
        exchange joins branch peers holding the same token slice; every
        peer computes the identical merged velocity, so branch shards
        stay replicated across the CFG dimension — bit-exact versus the
        batched path at the same shard size (asserted in
        serving/hybrid_demo.py).  Guided steps bypass the §11 feature
        cache (branch-specific KV cannot share a replicated snapshot).
        """
        req = graph.request
        g = float(req.guidance)
        txt_art = graph.artifacts[task.inputs[0]]
        lat_art = graph.artifacts[task.inputs[1]]
        out_art = graph.artifacts[task.outputs[0]]
        txt_c = txt_art.data[rank]["embeds"]
        txt_u = txt_art.data[rank]["embeds_uncond"]
        x_shard = lat_art.data[rank]["latent"]              # (N_loc, pd)
        spec = lat_art.fields["latent"]
        view = field_view(spec, layout)
        off, _ = view.slices[rank]
        n_total = spec.global_shape[0]

        sigmas = self._sigmas(req.steps)
        step = task.meta["step"]
        sigma_now = float(sigmas[step])
        sigma_next = float(sigmas[step + 1]) if step + 1 < req.steps \
            else 0.0
        ts = schedule.timestep_of_sigma(sigma_now)

        if layout.cfg == 1:
            if layout.degree == 1:
                def kv_gather(k, v, layer):
                    return k, v
            else:
                def kv_gather(k, v, layer):
                    K = comm.all_gather(desc, rank, np.asarray(k), axis=1)
                    V = comm.all_gather(desc, rank, np.asarray(v), axis=1)
                    return jnp.asarray(K), jnp.asarray(V)
            with self._region("gfdit.step.inputs"):
                x = jnp.asarray(x_shard)
                rows = jnp.stack([x, x])
                txt = jnp.stack([jnp.asarray(txt_c), jnp.asarray(txt_u)])
                t = jnp.array([ts, ts], jnp.float32)
            v = self._forward(rank, rows, t, txt, off, n_total, kv_gather,
                              grids=(self._grid(task),) * 2)
        else:
            b = layout.branch_of(rank)
            branch = desc.branches[b]
            i_local = branch.local_index(rank)
            merge = desc.merge[i_local]
            if layout.sp == 1:
                def kv_gather(k, v, layer):
                    return k, v
            else:
                def kv_gather(k, v, layer):
                    K = comm.all_gather(branch, rank, np.asarray(k),
                                        axis=1)
                    V = comm.all_gather(branch, rank, np.asarray(v),
                                        axis=1)
                    return jnp.asarray(K), jnp.asarray(V)
            with self._region("gfdit.step.inputs"):
                x = jnp.asarray(x_shard)
                txt = jnp.asarray(txt_c if b == 0 else txt_u)
                t = jnp.array([ts], jnp.float32)
            v_mine = self._forward(rank, x[None], t, txt[None], off,
                                   n_total, kv_gather,
                                   grids=(self._grid(task),))[0]
        with self._region("gfdit.step.update"):
            if layout.cfg == 1:
                v_c, v_u = v[0], v[1]
            else:
                # the one guidance-merge exchange: branch peers sharing
                # this token slice swap velocity shards; merge-group rank
                # order is branch order, so parts[0]=cond, parts[1]=uncond
                both = comm.all_gather(merge, rank,
                                       np.asarray(v_mine)[None], axis=0)
                v_c, v_u = both[0], both[1]
            merged = jnp.asarray(v_u) + g * (jnp.asarray(v_c)
                                             - jnp.asarray(v_u))
            new_x = schedule.flow_step(x, merged, sigma_now, sigma_next)
        with self._region("gfdit.step.fetch", bytes=new_x.nbytes):
            out_art.data[rank]["latent"] = np.asarray(new_x)
        out_art.data[rank]["sigma"] = np.float32(sigma_next)

    # ------------------------------------------------------------------
    def _decode(self, task, layout, rank, graph):
        lat_art = graph.artifacts[task.inputs[0]]
        out_art = graph.artifacts[task.outputs[0]]
        leader = layout.ranks[0]
        # the latent may be sharded over this task's layout (multi-rank
        # decode layouts); assemble each global range ONCE, in offset
        # order — under a CFG shape branch peers hold identical copies
        # of the same range (DESIGN.md §14), which must not be
        # concatenated twice.  For scalar-SP layouts offset order equals
        # rank order, so the assembly is byte-identical to the historic
        # rank-order concat.
        if lat_art.layout is not None and lat_art.layout.degree > 1:
            lview = field_view(lat_art.fields["latent"], lat_art.layout)
            by_off = {}
            for r in lat_art.layout.ranks:
                off, _ = lview.slices[r]
                if off not in by_off:
                    by_off[off] = lat_art.data[r]["latent"]
            tokens = np.concatenate(
                [by_off[o] for o in sorted(by_off)], axis=0)
        else:
            tokens = lat_art.data[leader]["latent"]           # (N, pd) full
        f, h, w, c = task.meta.get("latent_shape") or \
            self._infer_latent_shape(graph)
        lat = dit.unpatchify(jnp.asarray(tokens)[None],
                             (1, f, h, w, c), self.cfg.dit.patch_size)
        pixels = vae.decode(self.weights(rank)[2], lat, self.cfg)[0]
        out_art.data[leader]["pixels"] = np.asarray(pixels)

    def _infer_latent_shape(self, graph):
        req = graph.request
        f = max(1, (req.frames + 3) // 4) if req.frames > 1 else 1
        return (f, req.height // 8, req.width // 8, self.cfg.dit.in_channels)

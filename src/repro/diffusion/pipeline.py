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

    def _schedule(self, task, req):
        """(timestep, sigma now, sigma next) of a denoise task's step;
        the last step lands on sigma 0."""
        sigmas = self._sigmas(req.steps)
        step = task.meta["step"]
        s_now = float(sigmas[step])
        s_next = float(sigmas[step + 1]) if step + 1 < req.steps else 0.0
        return schedule.timestep_of_sigma(s_now), s_now, s_next

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
            self._step([(task, graph)], layout, rank, comm, desc)
        elif task.kind == "decode":
            if rank == layout.ranks[0]:
                self._decode(task, layout, rank, graph)
        else:
            raise ValueError(task.kind)

    def execute_packed(self, members, layout: ExecutionLayout, rank: int,
                       comm: GroupFreeComm, desc: GroupDescriptor):
        """Step packing (DESIGN.md §9): this rank's share of N
        batch-compatible denoise tasks as ONE batched forward (the
        control plane guarantees identical token shapes), with one set of
        GFC collectives amortized over the pack."""
        self._step(members, layout, rank, comm, desc)

    # ------------------------------------------------------------------
    def _step(self, members, layout, rank, comm, desc):
        """One denoise step of ``members`` ([(task, graph)]; a solo step
        is a pack of one) on this rank's token shard, as one forward.

        Each member contributes the rows this rank runs: [cond, uncond]
        for a guided request on a ``cfg == 1`` layout, its branch's row
        under a CFG split (DESIGN.md §14), else one row.  A member's
        latent shard and embeddings cross to the device once.  After the
        forward a guided member merges its two velocities (under a split,
        through the one exchange with its branch peer over
        ``desc.merge``), then each member takes its Euler update with its
        own sigma pair into its own output artifact."""
        task0, graph0 = members[0]
        spec = graph0.artifacts[task0.inputs[1]].fields["latent"]
        off, _ = field_view(spec, layout).slices[rank]
        branch = layout.branch_of(rank) if layout.cfg > 1 else None
        group = desc if branch is None else desc.branches[branch]
        rows_of, sched, t_rows, reqs, grids = [], [], [], [], []
        for task, graph in members:
            req = graph.request
            names = ("embeds", "embeds_uncond")[:req.cfg_branches]
            rows = names if branch is None else names[branch:branch + 1]
            t, s_now, s_next = self._schedule(task, req)
            rows_of.append(rows)
            sched.append((s_now, s_next))
            t_rows += [t] * len(rows)
            reqs += [req] * len(rows)
            grids += [self._grid(task)] * len(rows)

        with self._region("gfdit.step.inputs"):
            xs, x_rows, txt_rows = [], [], []
            for (task, graph), rows in zip(members, rows_of):
                x = jnp.asarray(graph.artifacts[task.inputs[1]]
                                .data[rank]["latent"])      # (N_loc, pd)
                embeds = graph.artifacts[task.inputs[0]].data[rank]
                xs.append(x)
                x_rows += [x] * len(rows)
                txt_rows += [jnp.asarray(embeds[r]) for r in rows]
            x_in = jnp.stack(x_rows)                       # (B, N_loc, pd)
            txt = jnp.stack(txt_rows)                      # (B, Lt, cond)
            t_in = jnp.array(t_rows, jnp.float32)
        v = self._forward(rank, x_in, t_in, txt, off, spec.global_shape[0],
                          self._kv_gather(members, layout, rank, comm,
                                          group, off),
                          guidance=self._scales(reqs), grids=tuple(grids))

        with self._region("gfdit.step.update"):
            new, i = [], 0
            for (_, graph), x, rows, (s_now, s_next) in zip(
                    members, xs, rows_of, sched):
                vel = v[i]
                if graph.request.cfg_branches == 2:
                    if branch is None:
                        v_c, v_u = vel, v[i + 1]
                    else:
                        # the one guidance-merge exchange: branch peers
                        # sharing this token slice swap velocity shards;
                        # merge-group order is branch order (cond, uncond)
                        both = comm.all_gather(
                            desc.merge[group.local_index(rank)], rank,
                            np.asarray(vel)[None], axis=0)
                        v_c, v_u = jnp.asarray(both[0]), jnp.asarray(both[1])
                    vel = v_u + float(graph.request.guidance) * (v_c - v_u)
                new.append(schedule.flow_step(x, vel, s_now, s_next))
                i += len(rows)
        with self._region("gfdit.step.fetch",
                          bytes=sum(n.nbytes for n in new)):
            for (task, graph), new_x, (_, s_next) in zip(members, new,
                                                         sched):
                out = graph.artifacts[task.outputs[0]].data[rank]
                out["latent"] = np.asarray(new_x)
                out["sigma"] = np.float32(s_next)

    def _kv_gather(self, members, layout, rank, comm, group, off):
        """The step's per-layer K/V gather over ``group`` (the whole
        layout, or this rank's CFG branch): none with one rank in it; the
        GFC all-gather; under a §11 refresh stamp, the gather that also
        stores each member's snapshot; on a hit, the stale snapshots with
        this step's fresh shard beside them as a :class:`ops.SplicedKV`,
        which the model splices (DESIGN.md §11, §12).  The stamp is the
        pack's one plane-made decision."""
        if layout.sp == 1:
            return lambda k, v, layer: (k, v)
        stamp = members[0][0].meta.get("cache")
        stores = [] if stamp is None else [
            g.artifacts[tk.meta["cache"]["art"]].data[rank]
            for tk, g in members]

        def gather(k, v, layer):
            K = comm.all_gather(group, rank, np.asarray(k), axis=1)
            V = comm.all_gather(group, rank, np.asarray(v), axis=1)
            # a refresh snapshots the gathered bytes themselves, so it is
            # bit-exact with an uncached step; every rank stores the same
            for store, k_j, v_j in zip(stores, K, V):
                store[f"k{layer}"], store[f"v{layer}"] = k_j, v_j
            return jnp.asarray(K), jnp.asarray(V)

        def hit(k, v, layer):
            K, V = snapshot_kv(stores, layer)
            return ops.SplicedKV(jnp.asarray(K), jnp.asarray(V), k, v, off)
        return hit if stamp is not None and stamp["mode"] == "hit" \
            else gather

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
        lat = dit.unpatchify(jnp.asarray(tokens)[None],
                             (1, *task.meta["latent_shape"]),
                             self.cfg.dit.patch_size)
        pixels = vae.decode(self.weights(rank)[2], lat, self.cfg)[0]
        out_art.data[leader]["pixels"] = np.asarray(pixels)

"""What decides ``correct``: the timed path's own outputs against the plain
reference (``gfbench.reference`` and the cell's architecture,
``bench/archs/<architecture>.py``), at the timed sizes.

Once the window has closed, a sample drawn from the seed is taken of
what the window produced, and copied to the host before the program is
freed:

* ``step``: denoise steps completed in the window, the latest one always
  among them.  ``step_gap`` compares the velocity the program applied,
  ``(x_out - x_in) / (sigma_next - sigma_now)``, with the reference
  velocity at the program's ``x_in`` (the architecture's rows, merged as
  it merges them); ``state_gap`` compares the state it produced,
  ``x_out``, with ``x_in + (sigma_next - sigma_now) * v_ref``;
* ``encode``: the text embeddings of those steps' requests (the
  unconditional one too where the architecture runs an unconditional
  row);
* ``latent0``: their initial noisy latents.

Each number is the widest relative L2 gap over its sample, against the
reference at float32 with every matmul at ``highest`` precision.  The
program runs at whatever precision it chooses.  The control puts the
reference computed in bfloat16 throughout in the program's place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from gfbench import reference as R

NUMBERS = ("step_gap", "state_gap", "encode_gap", "latent0_gap")


def _data(graph, aid):
    art = graph.artifacts[aid]
    return art.data[art.layout.ranks[0]]


def collect(cp, steps: list, seed: int, n_steps: int, arch) -> list:
    """Host copies of a seeded sample of the window's outputs.  ``steps``
    are the window's denoise completions (``window.denoise_steps``);
    ``arch`` says which rows a guided request runs."""
    rng = np.random.Generator(np.random.PCG64([seed % 2 ** 63, 7]))
    done = sorted((s for s in steps if s["finish"] is not None),
                  key=lambda s: s["finish"])
    pick = []
    if done:
        rest = done[:-1]
        k = min(n_steps - 1, len(rest))
        pick = [done[-1]] + [rest[i] for i in
                             rng.choice(len(rest), k, replace=False)]
    items, reqs = [], []
    for s in pick:
        g = cp.graphs[s["req"]]
        task = g.tasks[s["task"]]
        req = g.request
        items.append({"what": "step", "req": req.id, "step": task.step_index,
                      "steps": req.steps, "guidance": req.guidance,
                      "x_in": np.asarray(_data(g, task.inputs[1])["latent"]),
                      "x_out": np.asarray(
                          _data(g, task.outputs[0])["latent"])})
        if req.id not in reqs:
            reqs.append(req.id)
    for rid in reqs:
        g = cp.graphs[rid]
        enc = next(t for t in g.tasks.values() if t.kind == "encode")
        txt = _data(g, enc.outputs[0])
        r = g.request
        uncond = "uncond" in arch.rows(r.guidance)
        items.append({"what": "encode", "req": rid, "guidance": r.guidance,
                      "embeds": np.asarray(txt["embeds"]),
                      "embeds_uncond": (np.asarray(txt["embeds_uncond"])
                                        if uncond else None)})
        items.append({"what": "latent0", "req": rid, "steps": r.steps,
                      "latent": np.asarray(
                          _data(g, enc.outputs[1])["latent"])})
    return items


class Reference:
    """The reference's weights for one run and its answers, at float32
    or, as the control, at bfloat16; matmuls at ``highest``.  The DiT
    block is the architecture's (``arch``)."""

    def __init__(self, conf: dict, seed: int, arch):
        self.conf = conf
        self.arch = arch
        self.te = conf["text_encoder"]
        self.dit, self.txt = R.make_weights(conf, seed, arch)
        self._embeds: dict = {}

    def embeds(self, rid: str, uncond: bool, dtype):
        key = (rid, uncond, dtype)
        if key not in self._embeds:
            toks = R.prompt_tokens(rid, self.te)
            if uncond:
                toks = jnp.zeros_like(toks)
            self._embeds[key] = np.asarray(R.encode(
                self.txt, toks, self.te["rope_theta"], self.te["norm_eps"],
                dtype=dtype)[0])
        return self._embeds[key]

    def step(self, it: dict, dtype):
        """(velocity rows, sigma now, sigma next) at the program's
        ``x_in``: one row for each of the architecture's ``rows``."""
        s_now, s_next = R.sigma_pair(it["steps"], it["step"],
                                     self.conf["flow_shift"])
        emb = [self.embeds(it["req"], row == "uncond", dtype)
               for row in self.arch.rows(it["guidance"])]
        b = len(emb)
        x = jnp.asarray(it["x_in"], jnp.float32)
        t = jnp.full((b,), R.timestep(s_now), jnp.float32)
        v = self.arch.velocity(
            self.dit, jnp.broadcast_to(x, (b,) + x.shape), t,
            jnp.asarray(np.stack(emb)), it["guidance"], dtype=dtype)
        return np.asarray(v), s_now, s_next


def _control_state(x_in, v, s_now: float, s_next: float) -> np.ndarray:
    """The state a bfloat16 step produces: ``x + dt * v`` held in
    bfloat16."""
    bf = jnp.bfloat16
    x = jnp.asarray(x_in, bf) + jnp.asarray(s_next - s_now, bf) \
        * jnp.asarray(v, bf)
    return np.asarray(x.astype(jnp.float32))


def gaps(ref: Reference, items: list, control: bool = False) -> dict:
    """The widest gap of each kind over the sample: the program's outputs
    against the float32 reference, or (``control``) the bfloat16
    reference in the program's place."""
    out: dict = {}

    def widest(name, value):
        out[name] = max(out.get(name, 0.0), value)

    with jax.default_matmul_precision("highest"):
        for it in items:
            w = it["what"]
            if w == "step":
                rows, s_now, s_next = ref.step(it, jnp.float32)
                want = ref.arch.merge(rows, it["guidance"])
                dt = np.float32(s_next - s_now)
                x_in = np.asarray(it["x_in"], np.float32)
                if control:
                    got = ref.arch.merge(ref.step(it, jnp.bfloat16)[0],
                                         it["guidance"])
                    x_out = _control_state(x_in, got, s_now, s_next)
                else:
                    x_out = np.asarray(it["x_out"], np.float32)
                    got = (x_out - x_in) / dt
                widest("step_gap", R.rel_l2(got, want))
                widest("state_gap", R.rel_l2(
                    x_out, x_in.astype(np.float64)
                    + float(dt) * np.asarray(want, np.float64)))
            elif w == "encode":
                for uncond, name in ((False, "embeds"),
                                     (True, "embeds_uncond")):
                    if it[name] is None:
                        continue
                    want = ref.embeds(it["req"], uncond, jnp.float32)
                    got = (ref.embeds(it["req"], uncond, jnp.bfloat16)
                           if control else it[name])
                    widest("encode_gap", R.rel_l2(got, want))
            elif w == "latent0":
                n_tok, patch_dim = it["latent"].shape
                sigma0 = float(R.flow_sigmas(it["steps"],
                                             ref.conf["flow_shift"])[0])
                want = R.initial_latent(it["req"], n_tok, patch_dim, sigma0)
                got = (np.asarray(jnp.asarray(want, jnp.bfloat16),
                                  np.float64) if control else it["latent"])
                widest("latent0_gap", R.rel_l2(got, want))
    return out


def fault_readings(ref: Reference, items: list) -> dict:
    """``step_gap`` and ``state_gap`` as two faults would read them: a
    step that returns its state unchanged applies no velocity; where the
    architecture runs a guided step as a conditional and an unconditional
    row, a step with half of its batch (the unconditional row) left out
    applies the conditional velocity alone."""
    out: dict = {}

    def widest(name, value):
        out[name] = max(out.get(name, 0.0), value)

    with jax.default_matmul_precision("highest"):
        for it in items:
            if it["what"] != "step":
                continue
            rows, s_now, s_next = ref.step(it, jnp.float32)
            want = ref.arch.merge(rows, it["guidance"])
            dt = float(s_next - s_now)
            x_in = np.asarray(it["x_in"], np.float64)
            x_ref = x_in + dt * np.asarray(want, np.float64)
            widest("unchanged_state.step_gap", 1.0)
            widest("unchanged_state.state_gap", R.rel_l2(x_in, x_ref))
            if "uncond" in ref.arch.rows(it["guidance"]):
                widest("half_batch.step_gap", R.rel_l2(rows[0], want))
                widest("half_batch.state_gap", R.rel_l2(
                    x_in + dt * np.asarray(rows[0], np.float64), x_ref))
    return out


def verdict(found: dict, limits: dict) -> tuple[bool, dict]:
    """correct, and {number: [value, limit]}: every number found must be
    within its limit, and a step must have been compared."""
    table = {k: [found[k], limits[k]] for k in NUMBERS if k in found}
    ok = "step_gap" in found and all(v <= lim for v, lim in table.values())
    return ok, table

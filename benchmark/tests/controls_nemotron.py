#!/usr/bin/env python3
"""Sound readings and planted faults of the one-sub-layer cell's ``correct``,
several in one process, so that the reference's programs compile once:
``controls_eva.py``'s tool (its ``--run`` / ``--read``, ``--rehearse`` and
``--any-device``) with this tower's faults:

    python3 benchmark/tests/controls_nemotron.py --workload nemotron-b16-p16-s4096 \\
        --read sound:4300000001 --read no_decay:4300000001 --read fp8:4300000001 ...

The faults are planted in the PROGRAM's trace, the reference keeps the equations;
each must fail at least one of ``reference_nemotron.py``'s bounds:

    sound          nothing planted
    no_decay       the state-space decay left out: a_t = 1 (``ops/ssm.py ssm_scan`` is called with
                   A = 0, so every state keeps everything it was written)
    no_gate        the gate z left out of the gated norm (it is read as silu^-1(1): the norm
                   normalises y alone, and z's columns of the input projection get no gradient)
    gated_expert   a gated expert in place of relu2: the held routed experts compute
                   down(silu(up x) (up x)), the gate tied to the up projection, with its own derivative
    kv_shared      key / value heads shared wrongly: query head h reads head h % kv where it should
                   read head h // (heads / kv) (the queries' heads are permuted before the core and
                   its output permuted back)
    fp8            the state-space core's operands (x, B, C) and its output rounded to float8_e4m3's
                   precision by ``lax.reduce_precision`` on the way forward: the nearest precision
                   below the configuration's bfloat16

``--forced <seed>`` (beside at least one ``--read``, whose first it follows) reads
the seed sound and then shows what a near-tie of the router did to it: per routed layer, the tokens whose chosen experts differ between
the program (its own normalised stream through its own ``sigmoid_route``) and the
reference, the pooled (last) token of each sample caption with both selections and
the reference's margin between its last chosen and first unchosen score, and the
comparison again with the reference run on the PROGRAM's selection (what is left
then is arithmetic; what went was the near-ties).

Nothing here is read by the benchmark: it is how ``reference_nemotron.py``'s
control readings are reproduced.
"""

from __future__ import annotations

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import harness  # noqa: E402

eva = harness.load_module(os.path.join(BENCH_DIR, "tests", "controls_eva.py"))

VARIANTS = ("sound", "no_decay", "no_gate", "gated_expert", "kv_shared", "fp8")
SILU_INVERSE_OF_ONE = 1.2784645427610738  # z with silu(z) = 1


def plant(variant: str) -> None:
    """``variant``'s fault in place of whatever was planted before."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from distributed_sigmoid_loss_tpu.models import mixers, moe
    from distributed_sigmoid_loss_tpu.ops import ssm

    eva.lift()
    scan = ssm.ssm_scan

    def low(x):  # forwards; the cotangent passes as it is
        return x + jax.lax.stop_gradient(jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3) - x)

    if variant == "no_decay":
        eva._swap(ssm, "ssm_scan", lambda x, B, C, dt, A, D, **kw: scan(x, B, C, dt, jnp.zeros_like(A), D, **kw))
    elif variant == "fp8":
        eva._swap(ssm, "ssm_scan", lambda x, B, C, dt, A, D, **kw: low(scan(low(x), low(B), low(C), dt, A, D, **kw)))
    elif variant == "no_gate":
        norm = mixers.gated_group_norm
        eva._swap(mixers, "gated_group_norm", lambda y, z, *a: norm(y, jnp.full_like(z, SILU_INVERSE_OF_ONE), *a))
    elif variant == "gated_expert":
        F32 = jnp.float32

        def expert(xb, stacks, e, dt):
            ws = tuple(jax.lax.dynamic_index_in_dim(w, e, 0, keepdims=False).astype(dt) for w in stacks)
            up = jnp.dot(xb, ws[0], preferred_element_type=F32)
            hidden = (jax.nn.silu(up) * up).astype(dt)
            return ws, (up, hidden), jnp.dot(hidden, ws[1], preferred_element_type=F32)

        def expert_bwd(xb, ws, kept, dyw, dt):
            up, hidden = kept
            sig = jax.nn.sigmoid(up)
            d_hidden = jnp.dot(dyw, ws[1].T, preferred_element_type=F32)
            d_up = (d_hidden * up * sig * (2.0 + up * (1.0 - sig))).astype(dt)
            return jnp.dot(d_up, ws[0].T, preferred_element_type=F32), ((xb, d_up), (hidden, dyw))

        eva._swap(moe, "_expert_mlp", expert)
        eva._swap(moe, "_expert_mlp_bwd", expert_bwd)
    elif variant == "kv_shared":
        exact = nn.Dense.__call__

        def permuted(self, x):
            attn = self.parent
            grouped = "textual" in self.path and self.name in ("q", "out") and getattr(attn, "num_kv_heads", 0)
            if not grouped:
                return exact(self, x)
            h, kv = attn.num_heads, attn.num_kv_heads
            # position j of the core reads head j // (h / kv): give it the query head P[j] with P[j] % kv the same
            order = jnp.asarray(sorted(range(h), key=lambda j: (j % kv, j)))

            def heads(t, index):
                return t.reshape(*t.shape[:-1], h, -1)[..., index, :].reshape(t.shape)

            if self.name == "q":
                return heads(exact(self, x), order)
            return exact(self, heads(x, jnp.argsort(order)))

        eva._swap(nn.Dense, "__call__", permuted)
    elif variant != "sound":
        raise SystemExit(f"unknown variant {variant!r}: want one of {VARIANTS}")


def forced(job, cell, devices, kept: dict, seed: int) -> None:
    """The seed's sound reading, where the two selections differ, and the reading
    against the reference run on the program's selection."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distributed_sigmoid_loss_tpu.models.moe import sigmoid_route
    from distributed_sigmoid_loss_tpu.parallel.mesh import trace_on
    from distributed_sigmoid_loss_tpu.train.train_step import init_params

    plant("sound")
    reference, _ = kept.setdefault("modules", job.load_modules(cell.config))
    base = reference._base
    built = job.build_step(cell, devices)
    t, n = built.cfg.text, cell.traffic["check_rows"]
    k_init, k_batch = jax.random.split(jax.random.key(seed))
    params = init_params(k_init, built.model, built.batch_shapes, built.mesh)
    sample = jax.tree.map(lambda x: x[:n], job.make_batch(built, k_batch))
    encoder = params["textual"]["encoder"]
    routed = sorted(name for name in encoder if name.startswith("block") and "moe" in encoder[name])

    @jax.jit
    def chosen_by_program(params, sample):
        with trace_on(built.mesh):
            _, state = built.model.apply(
                {"params": params}, sample["images"], sample["tokens"], mutable=["intermediates"],
                capture_intermediates=lambda module, _: module.name == "ln1" and "textual" in module.path,
            )
        streams, out = state["intermediates"]["textual"]["encoder"], {}
        for name in routed:
            u, moe = streams[name]["ln1"]["__call__"][0], params["textual"]["encoder"][name]["moe"]
            idx, _ = sigmoid_route(u.reshape(-1, u.shape[-1]), moe["router"], moe["select_bias"], t.moe_num_selected, t.moe_route_scale)
            out[name] = idx.reshape(*u.shape[:-1], -1)
        return out

    @jax.jit
    @base._highest
    def chosen_by_reference(p, tokens):
        """Per routed layer: the reference's own selection (rows, s, k) and the margin between
        its last chosen and first unchosen score + bias (rows, s)."""
        def caption(row):
            x, out = p["token_embed"]["embedding"][row], {}
            for i in range(t.depth):
                block = p["encoder"][f"block{i}"]
                if "moe" in block:
                    u = reference.rms_norm(x, block["ln1"]["scale"], t.norm_eps)
                    scores = jax.nn.sigmoid(u @ block["moe"]["router"]) + block["moe"]["select_bias"]
                    top, idx = jax.lax.top_k(scores, t.moe_num_selected + 1)
                    out[f"block{i}"] = (idx[:, :-1], top[:, -2] - top[:, -1])
                x = jax.checkpoint(lambda x, block: reference.layer(x, block, t))(x, block)
            return out

        return jax.lax.map(caption, tokens)

    @jax.jit
    @base._highest
    def on_the_programs_selection(params, images, tokens, chosen):
        def loss_of(sel):
            zimg = base.l2_normalize(base.vision_tower(built.cfg.vision, params["visual"], images, sel["visual"]))
            ztxt = base.l2_normalize(reference.text_tower(t, params["textual"], tokens, sel["textual"], chosen))
            return base.sigmoid_loss(zimg, ztxt, sel["loss_scalars"][0], sel["loss_scalars"][1]), ztxt

        return jax.value_and_grad(loss_of, has_aux=True)(reference.first_blocks(params))

    loss, zimg, ztxt, grads = jax.device_get(job.make_system_check(built, reference)(params, sample))
    ref_loss, ref_grads = jax.device_get(reference.make_sample_grads(built.cfg)(params, sample["images"], sample["tokens"]))
    _, _, ref_ztxt = jax.device_get(
        reference.make_batch_loss(built.cfg, 1, np.arange(n)[None])(params, sample["images"], sample["tokens"]))

    def readings(ref_loss, ref_ztxt, ref_grads):
        errs = reference.tree_max_rel_err(grads, ref_grads)
        rows = np.linalg.norm(ztxt - ref_ztxt, axis=-1) / np.linalg.norm(ref_ztxt, axis=-1)
        return dict(text_rows=[float(r) for r in rows], embed_err_text=reference.max_rel_err(ztxt, ref_ztxt),
                    sample_loss_err=abs(float(loss) - float(ref_loss)) / abs(float(ref_loss)),
                    grad_err=max(errs.values()), grad_err_worst_leaf=max(errs, key=errs.get), grad_errs=errs)

    eva.say("forced.sound", seed=seed, **readings(ref_loss, ref_ztxt, ref_grads))  # the reference on its own selection
    mine = jax.device_get(chosen_by_program(params, sample))
    theirs = jax.device_get(chosen_by_reference(params["textual"], sample["tokens"]))
    held = t.moe_experts_held or t.moe_experts
    for name in routed:
        a, (b, margin) = np.sort(mine[name], -1), theirs[name]
        b = np.sort(b, -1)
        differ = (a != b).any(-1)  # (rows, s)
        held_differ = (np.where(a < held, a, -1) != np.where(b < held, b, -1)).any(-1)  # a held expert gained or lost
        eva.say("forced.selection", seed=seed, layer=name, tokens=int(differ.size),
                tokens_whose_experts_differ=int(differ.sum()), tokens_whose_held_experts_differ=int(held_differ.sum()),
                pooled_tokens=[dict(caption=r, program=a[r, -1].tolist(), reference=b[r, -1].tolist(),
                                    reference_margin=float(margin[r, -1]), held_differ=bool(held_differ[r, -1]))
                               for r in range(n)],
                median_margin=float(np.median(margin)))
    (forced_loss, forced_ztxt), forced_grads = jax.device_get(
        on_the_programs_selection(params, sample["images"], sample["tokens"], {k: jnp.asarray(v) for k, v in mine.items()}))
    eva.say("forced.read", seed=seed, **readings(forced_loss, forced_ztxt, forced_grads))


def main(argv=None) -> int:
    """``controls_eva.py``'s ``main`` with this tower's faults, and ``--forced <seed>`` (several) besides."""
    argv = list(sys.argv[1:] if argv is None else argv)
    seeds = [int(argv[i + 1]) for i, a in enumerate(argv) if a == "--forced"]
    rest = [a for i, a in enumerate(argv) if a != "--forced" and (i == 0 or argv[i - 1] != "--forced")]
    read = eva.read

    def read_then_forced(job, cell, devices, kept, variant, seed):
        read(job, cell, devices, kept, variant, seed)
        while seeds:  # after the first read: the modules are loaded and the device is this process's
            forced(job, cell, devices, kept, seeds.pop(0))

    eva.VARIANTS, eva.plant, eva.read = VARIANTS, plant, read_then_forced
    try:
        return eva.main(rest)
    finally:
        eva.read = read


if __name__ == "__main__":
    raise SystemExit(main())

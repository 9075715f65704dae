"""Flagship transformer-LM training example.

Three modes:

- ``--mode spmd`` (default): the TPU-idiomatic path — one process, all
  chips, the whole train step shard_mapped over a (data, seq, tensor)
  mesh built from ``--mesh data=2,seq=2,tensor=2`` (axes riding DCN go
  first; see ``horovod_tpu.parallel.mesh.multislice_mesh`` for
  multi-slice pods). ``--sp-layout zigzag`` load-balances the causal
  ring.
- ``--mode eager``: the Horovod-style path — one process per chip under
  ``tpurun``, gradients reduced through ``hvd.DistributedOptimizer``
  (``--delta-adasum`` for the delta-model Adasum form).
- ``--mode pp``: the flagship through the memory-bounded 1F1B pipeline
  (``--stages``, ``--n-micro``).

The block is told by flags, in every mode (the pipeline runs the same
block; its first and last stage refuse ``--untied-head`` and ``--n-loops``
above 1 by name): ``--positions rope``, ``--ffn swiglu``,
``--norm sandwich``, ``--untied-head``, ``--n-loops 4`` make a looped
decoder of the Ouro kind (benchmark/configs/ouro-2.6b.json), whose mean
exit share of every pass goes to the gauge ``hvd_tpu_lm_exit_share``. In
spmd mode the share of the gradient bytes whose all-reduce the step issues
inside its backward scan goes to ``hvd_tpu_lm_grad_reduce_in_backward_share``,
and with ``--attention flash`` which kernel the local attention call takes,
at which blocks, to ``hvd_tpu_attn_kernel``.

``--kv-heads``, ``--head-size``, ``--qk-norm``, ``--attn-gate`` and
``--embed-scale`` are the block's too. ``--pattern sssf`` makes the stack a
per-layer pattern (spmd mode; the pipeline refuses it by name): ``s`` a
sliding-window layer (``--window``), ``f`` a full-attention layer that does
not rotate, ``a`` one that does, ``c`` a layer whose mixer is the gated short
convolution (three taps; ``--pattern caccc --dense-layers 1`` with
``--kv-heads``, ``--qk-norm`` is a model of the kind of
benchmark/configs/lfm2-8b-a1b.json, whose layers by kind of mixer go to the
gauge ``hvd_tpu_lm_layers``), repeated over ``--n-layers``; the capital
letters are layers of ONE sublayer, as ``hybrid_override_pattern`` writes
them: ``M`` a Mamba-2 state-space mixer alone (``--ssm-heads``,
``--ssm-head-dim``, ``--ssm-state``, ``--ssm-groups``, ``--ssm-chunk``,
``--conv-kernel``; the chunks of a row go to the gauge
``hvd_tpu_lm_scan_chunks``, the form of the scan to
``hvd_tpu_lm_scan_kernel``), ``*`` attention alone, which rotates nothing, and
``E`` the routed-expert FFN alone
(``--pattern "MEMEM*E" --expert-ffn relu2 --shared-experts 1 --shared-ff
...`` is a model of the kind of
benchmark/configs/nemotron-3-nano-30b-a3b.json); ``l`` is a layer whose
mixer is latent attention (``--q-lora-rank``, ``--kv-lora-rank``,
``--qk-nope-dim``, ``--qk-rope-dim``, ``--v-head-dim``), and ``--mtp-depth 1
--mtp-weight 0.1`` adds the multi-token-prediction module after the pattern,
whose term of the last step goes to the gauge ``hvd_tpu_lm_mtp_loss`` and
whose weight to ``hvd_tpu_lm_mtp_weight`` (``--pattern l --dense-layers 1
--untied-head --shared-experts 1 --route-scale 2.5 --mtp-depth 1`` is a model
of the kind of benchmark/configs/joyai-llm-flash.json); of the other letters
the first
``--dense-layers`` keep
the dense FFN, the others route ``--top-k`` of ``--experts`` sigmoid-scored
SwiGLU experts of ``--expert-ff`` beside ``--shared-experts``, of which
this program holds ``--experts-held`` from ``--first-expert`` on
(benchmark/configs/trinity-mini.json is such a model). The step then
returns the experts' counts beside the loss: the held share, the fullest
expert's load and the dropped assignments (0) go to the gauges
``hvd_tpu_moe_*``.

Synthetic data; prints tokens/sec. Mirrors the reference's synthetic
benchmark scripts (examples/*_synthetic_benchmark.py) for the LM workload.
"""

from __future__ import annotations

import argparse
import collections
import time


def parse_mesh(spec: str) -> dict:
    out = {}
    for part in spec.split(","):
        k, v = part.split("=")
        out[k.strip()] = int(v)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["spmd", "eager", "pp"],
                    default="spmd")
    ap.add_argument("--stages", type=int, default=None,
                    help="pp mode: pipeline stages (default: all devices)")
    ap.add_argument("--n-micro", type=int, default=4,
                    help="pp mode: microbatches per step")
    ap.add_argument("--mesh", default=None,
                    help="e.g. data=2,seq=2,tensor=2 (spmd mode)")
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--n-layers", type=int, default=4)
    ap.add_argument("--n-heads", type=int, default=8)
    ap.add_argument("--d-ff", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--attention", default="ring",
                    choices=["ring", "ulysses", "flash"])
    ap.add_argument("--sp-layout", default="contiguous",
                    choices=["contiguous", "zigzag"],
                    help="sequence-parallel data layout; zigzag balances "
                         "causal ring work exactly across ranks (tokens/"
                         "targets are permuted with zigzag_indices here)")
    ap.add_argument("--moe", action="store_true")
    ap.add_argument("--remat", default="none",
                    choices=["none", "block", "attention"])
    ap.add_argument("--positions", default="none", choices=["none", "rope"])
    ap.add_argument("--rope-theta", type=float, default=10000.0)
    ap.add_argument("--ffn", default="gelu", choices=["gelu", "swiglu"])
    ap.add_argument("--norm", default="pre", choices=["pre", "sandwich"])
    ap.add_argument("--norm-eps", type=float, default=1e-6)
    ap.add_argument("--untied-head", action="store_true")
    ap.add_argument("--n-loops", type=int, default=1,
                    help="passes over the stack with the same weights; "
                         "above 1 every pass ends in the head and a "
                         "learned exit gate")
    ap.add_argument("--exit-entropy-weight", type=float, default=0.1)
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="K and V heads, a divisor of --n-heads (0: as many)")
    ap.add_argument("--head-size", type=int, default=0,
                    help="a head's width where it is not d_model / n_heads")
    ap.add_argument("--qk-norm", action="store_true")
    ap.add_argument("--attn-gate", action="store_true")
    ap.add_argument("--embed-scale", type=float, default=1.0)
    ap.add_argument("--pattern", default="",
                    help="one letter a layer of a period: s (window), f "
                         "(full attention, no rotation), a (full attention, "
                         "rotated), c (the gated short convolution), e.g. "
                         "sssf, caccc, l (latent attention); layers of one "
                         "sublayer: M (Mamba-2 "
                         "alone), * (attention alone, no rotation), E (the "
                         "routed experts alone), e.g. 'MEMEM*E'")
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--dense-layers", type=int, default=1)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--expert-ff", type=int, default=0)
    ap.add_argument("--shared-experts", type=int, default=0)
    ap.add_argument("--shared-ff", type=int, default=0,
                    help="the shared experts' width together where it is "
                         "not --shared-experts x --expert-ff")
    ap.add_argument("--expert-ffn", choices=["swiglu", "relu2"],
                    default="swiglu")
    ap.add_argument("--ssm-heads", type=int, default=0)
    ap.add_argument("--ssm-head-dim", type=int, default=64)
    ap.add_argument("--ssm-state", type=int, default=128)
    ap.add_argument("--ssm-groups", type=int, default=1)
    ap.add_argument("--ssm-chunk", type=int, default=128)
    ap.add_argument("--conv-kernel", type=int, default=3)
    ap.add_argument("--q-lora-rank", type=int, default=0)
    ap.add_argument("--kv-lora-rank", type=int, default=0)
    ap.add_argument("--qk-nope-dim", type=int, default=0)
    ap.add_argument("--qk-rope-dim", type=int, default=0)
    ap.add_argument("--v-head-dim", type=int, default=0)
    ap.add_argument("--mtp-depth", type=int, default=0,
                    help="1: the multi-token-prediction module after the "
                         "pattern, one more block and a second loss")
    ap.add_argument("--mtp-weight", type=float, default=0.1)
    ap.add_argument("--route-scale", type=float, default=1.0)
    ap.add_argument("--experts-held", type=int, default=0)
    ap.add_argument("--first-expert", type=int, default=0)
    ap.add_argument("--router-bias-rate", type=float, default=0.0)
    ap.add_argument("--delta-adasum", action="store_true",
                    help="eager mode: delta-model Adasum (local optimizer "
                         "step first, Adasum on the parameter delta)")
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.common.env import use_compile_cache
    use_compile_cache()
    from horovod_tpu.models.transformer import (LayerKind,
                                                TransformerConfig,
                                                init_params, lean_lm_loss,
                                                make_train_step,
                                                shard_params)

    pattern = {}
    if args.pattern:
        kinds = {"s": dict(window=args.window), "f": dict(rope=False),
                 "a": {}, "c": dict(mixer="conv"), "l": dict(mixer="mla")}
        alone = {"M": dict(mixer="mamba2", experts=None),
                 "*": dict(rope=False, experts=None),
                 "E": dict(mixer="none", experts=True)}
        letters = [args.pattern[i % len(args.pattern)]
                   for i in range(args.n_layers)]
        pattern = dict(
            layers=tuple(
                LayerKind(**alone[letter]) if letter in alone else
                LayerKind(**kinds[letter], experts=i >= args.dense_layers)
                for i, letter in enumerate(letters)),
            moe_top_k=args.top_k, d_ff_expert=args.expert_ff or args.d_ff,
            expert_ffn=args.expert_ffn, d_ff_shared=args.shared_ff,
            conv_kernel=args.conv_kernel, ssm_heads=args.ssm_heads,
            ssm_head_dim=args.ssm_head_dim, ssm_state=args.ssm_state,
            ssm_groups=args.ssm_groups, ssm_chunk=args.ssm_chunk,
            q_lora_rank=args.q_lora_rank, kv_lora_rank=args.kv_lora_rank,
            qk_nope_dim=args.qk_nope_dim, qk_rope_dim=args.qk_rope_dim,
            v_head_dim=args.v_head_dim, mtp_depth=args.mtp_depth,
            mtp_weight=args.mtp_weight,
            n_shared_experts=args.shared_experts,
            route_scale=args.route_scale, experts_held=args.experts_held,
            first_expert=args.first_expert,
            router_bias_rate=args.router_bias_rate)
    cfg = TransformerConfig(
        n_kv_heads=args.kv_heads, head_size=args.head_size,
        qk_norm=args.qk_norm, attn_gate=args.attn_gate,
        embed_scale=args.embed_scale, n_experts=args.experts, **pattern,
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff, max_seq=args.seq,
        dtype=jnp.bfloat16, attention=args.attention,
        sp_layout=args.sp_layout, use_moe=args.moe, remat=args.remat,
        positions=args.positions, rope_theta=args.rope_theta, ffn=args.ffn,
        norm=args.norm, norm_eps=args.norm_eps,
        tie_embeddings=not args.untied_head, n_loops=args.n_loops,
        exit_entropy_weight=args.exit_entropy_weight)
    opt = optax.adamw(3e-4)
    rng = np.random.RandomState(0)
    # seq+1 raw tokens so the shifted input/target windows are exactly
    # --seq long (keeps sequence sharding divisible)
    tokens = rng.randint(0, args.vocab, size=(args.batch, args.seq + 1))
    inputs = jnp.asarray(tokens[:, :-1])
    targets = jnp.asarray(tokens[:, 1:])

    if args.mode == "pp":
        # the flagship through the memory-bounded 1F1B pipeline: embedding
        # on stage 0, n_layers/stages layers per stage, tied-embedding
        # head + lean loss on the last stage (docs/parallelism.md)
        from jax.sharding import Mesh
        from horovod_tpu.models.transformer import (make_pp_train_step,
                                                    pp_param_specs)
        n_stages = args.stages or len(jax.devices())
        mesh = Mesh(np.array(jax.devices()[:n_stages]), ("pipe",))
        # first: the builder refuses by name what its stages cannot run
        step = make_pp_train_step(mesh, cfg, opt, n_micro=args.n_micro)
        specs = pp_param_specs(cfg)
        params = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            init_params(jax.random.PRNGKey(0), cfg), specs)
        opt_state = opt.init(params)
        params, opt_state, loss = step(params, opt_state, inputs, targets)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            params, opt_state, loss = step(params, opt_state, inputs,
                                           targets)
        loss = float(loss)
        dt = (time.perf_counter() - t0) / args.steps
    elif args.mode == "spmd":
        from horovod_tpu.parallel.mesh import training_mesh
        # the flagship step names all three axes; absent ones get size 1
        mesh_spec = {"data": len(jax.devices()), "seq": 1, "tensor": 1}
        if args.mesh:
            mesh_spec.update({"data": 1})
            mesh_spec.update(parse_mesh(args.mesh))
        mesh = training_mesh(mesh_spec)
        params = shard_params(init_params(jax.random.PRNGKey(0), cfg),
                              mesh, cfg)
        step = make_train_step(mesh, cfg, opt)
        # how much of the gradient exchange the step issues under its
        # backward pass, by bytes: a property of the mesh and the model
        from horovod_tpu.metrics import registry
        from horovod_tpu.models.transformer import (
            grad_reduce_in_backward_share)
        in_backward = grad_reduce_in_backward_share(mesh, cfg)
        registry().gauge("hvd_tpu_lm_grad_reduce_in_backward_share").set(
            in_backward,
            mesh=",".join(f"{a}={n}" for a, n in mesh.shape.items()))
        by_mixer = collections.Counter(k.mixer for k in cfg.layers)
        for mixer, n in by_mixer.items():
            registry().gauge("hvd_tpu_lm_layers").set(n, mixer=mixer)
        scan = None
        if cfg.has_mamba:
            registry().gauge("hvd_tpu_lm_scan_chunks").set(
                args.seq // cfg.ssm_chunk, chunk=str(cfg.ssm_chunk))
            # which form of the scan the step runs on this backend: a
            # property of the shape a chip holds
            from horovod_tpu.parallel.ssd import scan_form
            rows = args.batch // mesh.shape["data"]
            scan = scan_form(
                (rows, args.seq, cfg.ssm_heads, cfg.ssm_head_dim),
                (rows, args.seq, cfg.ssm_groups, cfg.ssm_state),
                cfg.ssm_chunk)
            registry().gauge("hvd_tpu_lm_scan_kernel").set(1, **scan)
        attn = None
        if cfg.attention == "flash" and mesh.shape["seq"] == 1:
            # what the step's local attention call runs on this backend, and
            # at which blocks: a property of the shape a chip holds
            from horovod_tpu.parallel.flash_attention import attention_kernel
            rows = args.batch // mesh.shape["data"]
            local, local_kv = ((rows, h // mesh.shape["tensor"], args.seq,
                                cfg.head_dim)
                               for h in (cfg.n_heads, cfg.kv_heads))
            # one label set a kind of attention layer (a stack with no
            # pattern is attention throughout; one of conv layers alone
            # calls no kernel); the report keeps the last
            windows = {k.window for k in cfg.layers
                       if k.mixer == "attention"} if cfg.layers else {0}
            for window in sorted(windows):
                attn = attention_kernel(local, local_kv, causal=True,
                                        under_remat=cfg.remat != "none",
                                        window=window)
                registry().gauge("hvd_tpu_attn_kernel").set(1, **attn)
            if cfg.has_mla:     # q/k heads of one size, v heads of another
                latent = (rows, cfg.n_heads, args.seq,
                          cfg.qk_nope_dim + cfg.qk_rope_dim)
                attn = attention_kernel(latent, latent, causal=True,
                                        under_remat=cfg.remat != "none",
                                        v_head_size=cfg.v_head_dim)
                registry().gauge("hvd_tpu_attn_kernel").set(1, **attn)
        if cfg.mtp_depth:
            registry().gauge("hvd_tpu_lm_mtp_weight").set(cfg.mtp_weight)
        opt_state = opt.init(params)
        tok_sh = NamedSharding(mesh, P("data", "seq"))
        if args.sp_layout == "zigzag":
            # zigzag data layout: only the tokens must be permuted to
            # match (the per-token loss mean is permutation-invariant, and
            # with --positions rope every shard rotates by the global
            # positions of the stripes it holds)
            from horovod_tpu.parallel import zigzag_indices
            idx, _ = zigzag_indices(args.seq, mesh_spec.get("seq", 1))
            inputs = jnp.take(inputs, idx, axis=1)
            targets = jnp.take(targets, idx, axis=1)
        inputs = jax.device_put(inputs, tok_sh)
        targets = jax.device_put(targets, tok_sh)
        # (with routed-expert layers the step returns their counts too)
        params, opt_state, loss, *stats = step(params, opt_state, inputs,
                                               targets)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            params, opt_state, loss, *stats = step(params, opt_state,
                                                   inputs, targets)
        loss = float(loss)
        dt = (time.perf_counter() - t0) / args.steps
        if stats and "mtp_loss" in stats[0]:
            # logged with the loss: the second head's term of it
            registry().gauge("hvd_tpu_lm_mtp_loss").set(
                float(stats[0]["mtp_loss"]))
        if stats and "expert_counts" in stats[0]:
            # logged with the loss: where the router sent the last step
            from horovod_tpu.models.transformer import routing_stats
            routing = routing_stats(stats[0]["expert_counts"], cfg,
                                    args.batch * args.seq)
            for name, gauge in (
                    ("held_share", "hvd_tpu_moe_held_assignment_share"),
                    ("buffer_rows", "hvd_tpu_moe_buffer_rows"),
                    ("buffer_fill", "hvd_tpu_moe_buffer_fill"),
                    ("load_max_over_mean",
                     "hvd_tpu_moe_expert_load_max_over_mean"),
                    ("row_sum_rows_over_live",
                     "hvd_tpu_moe_row_sum_rows_over_live")):
                for layer, value in enumerate(routing[name]):
                    registry().gauge(gauge).set(value, layer=str(layer))
            registry().gauge("hvd_tpu_moe_row_sum").set(
                1, form=routing["row_sum_form"])
            registry().gauge("hvd_tpu_moe_dropped_assignments").set(
                routing["dropped"])
    else:
        import horovod_tpu as hvd
        hvd.init()
        if args.delta_adasum:
            # delta-model Adasum (torch/optimizer.py:196-364): local
            # optimizer step first, scale-invariant VHDD on the delta
            opt = hvd.DistributedDeltaAdasumOptimizer(opt)
        else:
            opt = hvd.DistributedOptimizer(opt, op=hvd.Average)
        params = init_params(jax.random.PRNGKey(0), cfg)
        params = hvd.broadcast_parameters(params, root_rank=0)
        opt_state = opt.init(params)
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p, x, y: lean_lm_loss(p, x, y, cfg)))
        # per-rank shard of the global batch
        per = max(args.batch // hvd.size(), 1)
        lo = hvd.rank() * per
        bx, by = inputs[lo:lo + per], targets[lo:lo + per]
        loss, grads = grad_fn(params, bx, by)
        params, opt_state = opt.update_and_apply(grads, opt_state, params)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            loss, grads = grad_fn(params, bx, by)
            params, opt_state = opt.update_and_apply(grads, opt_state,
                                                     params)
        loss = float(loss)
        dt = (time.perf_counter() - t0) / args.steps

    toks = args.batch * args.seq
    report = {"mode": args.mode, "loss": round(loss, 4),
              "step_ms": round(dt * 1e3, 2),
              "tokens_per_sec": round(toks / dt, 1)}
    if args.mode == "spmd":
        report["grad_reduce_in_backward_share"] = round(in_backward, 4)
        if attn:
            report["attn_kernel"] = attn
        if scan:
            report["scan_kernel"] = scan
        if by_mixer:
            report["layers_by_mixer"] = dict(sorted(by_mixer.items()))
        if stats and "mtp_loss" in stats[0]:
            report["mtp_loss"] = round(float(stats[0]["mtp_loss"]), 4)
        if stats and "expert_counts" in stats[0]:
            report["routing"] = {
                k: v if isinstance(v, str) else np.round(v, 4).tolist()
                for k, v in routing.items()}
    if cfg.n_loops > 1:
        # logged with the loss: whether the exit gate has collapsed
        from horovod_tpu.metrics import registry
        from horovod_tpu.models.transformer import exit_distribution
        share = np.asarray(exit_distribution(
            jax.device_get(params), tokens[:1, :-1], cfg))
        gauge = registry().gauge("hvd_tpu_lm_exit_share")
        for t, p_t in enumerate(share, 1):
            gauge.set(float(p_t), **{"pass": str(t)})
        report["exit_share"] = [round(float(p_t), 4) for p_t in share]
    print(report)


if __name__ == "__main__":
    main()

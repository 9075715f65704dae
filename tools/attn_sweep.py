#!/usr/bin/env python3
"""Sweep of the stock attention kernels' geometry at the shapes the
benchmark's LM cells run (ISSUE 31; the table it gave is in PERF.md section
6). Nothing a cell runs imports this file; the rule the sweep led to is
``horovod_tpu/parallel/flash_attention.py splash_geometry``.

Three stages, the first without a chip:

``fit``     compiles every candidate HERE for a described v5e:2x2 (the
            installed libtpu's compiler, nothing runs) and writes which fit:
            an over-large block fails with ``RESOURCE_EXHAUSTED ... vmem``.
``time``    on the chip: the forward alone and forward + backward of the
            attention call alone, milliseconds a call, for every candidate
            that fit; then the stages' winners composed, today's geometry
            and the stock flash kernel.
``errors``  on the chip: dq, dk, dv of chosen geometries against a float32
            materialized attention at ``highest`` precision, 8 seeds.

Shapes: ``lm`` is 4 x 16 x 2048 x 128, differentiated plainly; ``loop`` is
1 x 16 x 4096 x 128 under ``jax.checkpoint``, so its backward runs the
forward again, as the looped cell's does; ``band`` and ``gqa`` (ISSUE 32)
are 1 x 32/4 x 8192 x 128 under ``jax.checkpoint``, 32 query heads over 4
KV heads through the kernel's MQA form a KV head, ``band`` under a window
of 2048 and ``gqa`` causal: the two kinds of layer of the sparse-expert
cell; ``head64`` (ISSUE 34) is 2 x 32/8 x 8192 x 64 under
``jax.checkpoint``, causal: the one attention layer of the conv/attention
cell, heads half as wide as the kernel's 128 lanes; ``mla`` (ISSUE 41) is
2 x 32 x 8192 causal under ``jax.checkpoint`` with q and k heads of 192 and
v heads of 128 (``v_head``), the latent-attention cell's call, and
``mla_pad256`` the same with q and k zero-padded to 256 OUTSIDE the kernel
(``pad_qk``: the scores do not change), ``mla_4k`` one row of 4,096 of it for
``errors``; ``short`` (8 rows of 1024) and ``full`` (``lm`` without the causal
mask) are in no cell and are measured on ``--geometry``'s alone. ``--rehearse`` runs the same code interpreted on
the CPU at T = 256: a test of the script, never a time.

    python tools/attn_sweep.py fit --out .bench_tree/attn_fit.json
    chiprun --chips 1 -- python tools/attn_sweep.py time \\
        --fit .bench_tree/attn_fit.json --out chiprun_out/attn_sweep.json
    ... errors --winners chiprun_out/attn_sweep.json --out <json>
    ... time --shapes full short --geometry 1024/1024/512:1024/1024/1024:fused
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

HEADS, HEAD = 16, 128
# "heads", "kv_heads" (default HEADS of each), "head" (default HEAD),
# "v_head" (default "head"), "pad_qk" (q and k zero-padded to it outside
# the kernel; default none) and "window" (default none)
SHAPES = {"lm": {"rows": 4, "t": 2048, "remat": False, "causal": True},
          "loop": {"rows": 1, "t": 4096, "remat": True, "causal": True},
          "band": {"rows": 1, "t": 8192, "remat": True, "causal": True,
                   "heads": 32, "kv_heads": 4, "window": 2048},
          "gqa": {"rows": 1, "t": 8192, "remat": True, "causal": True,
                  "heads": 32, "kv_heads": 4},
          "head64": {"rows": 2, "t": 8192, "remat": True, "causal": True,
                     "heads": 32, "kv_heads": 8, "head": 64},
          "mla": {"rows": 2, "t": 8192, "remat": True, "causal": True,
                  "heads": 32, "head": 192, "v_head": 128},
          "mla_pad256": {"rows": 2, "t": 8192, "remat": True, "causal": True,
                         "heads": 32, "head": 192, "v_head": 128,
                         "pad_qk": 256},
          # the same call over half the positions and one row, for
          # ``errors``: the float32 scores of 8,192 positions do not fit
          "mla_4k": {"rows": 1, "t": 4096, "remat": True, "causal": True,
                     "heads": 32, "head": 192, "v_head": 128},
          # in no cell; measured on --geometry's alone
          "short": {"rows": 8, "t": 1024, "remat": False, "causal": True},
          "full": {"rows": 4, "t": 2048, "remat": False, "causal": False}}
REHEARSAL = {"lm": {"rows": 2, "t": 256, "remat": False, "causal": True},
             "loop": {"rows": 1, "t": 256, "remat": True, "causal": True},
             "band": {"rows": 1, "t": 256, "remat": True, "causal": True,
                      "heads": 4, "kv_heads": 2, "window": 64},
             "gqa": {"rows": 1, "t": 256, "remat": True, "causal": True,
                     "heads": 4, "kv_heads": 2},
             "head64": {"rows": 2, "t": 256, "remat": True, "causal": True,
                        "heads": 4, "kv_heads": 2, "head": 64},
             "mla": {"rows": 1, "t": 256, "remat": True, "causal": True,
                     "heads": 2, "head": 192, "v_head": 128},
             "mla_pad256": {"rows": 1, "t": 256, "remat": True,
                            "causal": True, "heads": 2, "head": 192,
                            "v_head": 128, "pad_qk": 256},
             "mla_4k": {"rows": 1, "t": 256, "remat": True, "causal": True,
                        "heads": 2, "head": 192, "v_head": 128},
             "short": {"rows": 2, "t": 128, "remat": False, "causal": True},
             "full": {"rows": 2, "t": 256, "remat": False, "causal": False}}
BLOCKS = (512, 1024, 2048)
REHEARSAL_BLOCKS = (128, 256)


@dataclasses.dataclass(frozen=True)
class Geometry:
    """One way to build the attention call. ``fwd`` and ``dkv`` are (block_q,
    block_kv, block_kv_compute); ``dq`` is (block_q, block_kv), None where
    the backward is one fused kernel. ``kernel`` "flash" is the stock flash
    kernel with every block at ``fwd[0]``."""
    kernel: str
    fwd: tuple
    dkv: tuple
    dq: tuple | None

    @property
    def fused(self) -> bool:
        return self.kernel == "splash" and self.dq is None

    @property
    def id(self) -> str:
        if self.kernel == "flash":
            return f"flash:{self.fwd[0]}"
        tail = "fused" if self.fused else "dq:%d/%d" % self.dq
        return "splash:%d/%d/%d|dkv:%d/%d/%d|%s" % (*self.fwd, *self.dkv, tail)


def triples(blocks, t):
    """(block_q, block_kv, block_kv_compute): the compute slice divides the
    kv block, nothing exceeds T."""
    return [(q, kv, c) for q, kv, c in itertools.product(blocks, repeat=3)
            if c <= kv and kv % c == 0 and max(q, kv) <= t]


def attention(geo: Geometry, shape: dict, interpret: bool):
    """q [rows, heads, t, head], k [rows, kv_heads, t, head], v [rows,
    kv_heads, t, v_head] -> the attention, built as ``flash_attention_local``
    builds it but for the geometry."""
    if shape.get("pad_qk"):
        inner = attention(geo, {**shape, "pad_qk": 0}, interpret)
        pad = ((0, 0),) * 3 + ((0, shape["pad_qk"] - shape["head"]),)
        return lambda q, k, v: inner(jnp.pad(q, pad), jnp.pad(k, pad), v)
    t, causal = shape["t"], shape["causal"]
    heads = shape.get("heads", HEADS)
    group = heads // shape.get("kv_heads", heads)
    scale = 1.0 / math.sqrt(shape.get("head", HEAD))
    if geo.kernel == "flash":
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            BlockSizes, flash_attention)
        b = geo.fwd[0]
        bs = BlockSizes(block_q=b, block_k_major=b, block_k=b, block_b=1,
                        block_q_major_dkv=b, block_k_major_dkv=b,
                        block_k_dkv=b, block_q_dkv=b, block_k_major_dq=b,
                        block_k_dq=b, block_q_dq=b)
        return lambda q, k, v: flash_attention(
            q, k, v, causal=causal, sm_scale=scale, block_sizes=bs)
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    dq = ({} if geo.fused else
          {"block_q_dq": geo.dq[0], "block_kv_dq": geo.dq[1]})
    bs = sk.BlockSizes(
        block_q=geo.fwd[0], block_kv=geo.fwd[1], block_kv_compute=geo.fwd[2],
        block_q_dkv=geo.dkv[0], block_kv_dkv=geo.dkv[1],
        block_kv_dkv_compute=geo.dkv[2], use_fused_bwd_kernel=geo.fused, **dq)
    with jax.ensure_compile_time_eval():
        if shape.get("window"):
            one = sm.LocalMask((t, t), (shape["window"] - 1, 0), 0)
        else:
            one = (sm.CausalMask if causal else sm.FullMask)((t, t))
        if group == 1:
            kernel = sk.make_splash_mha(
                sm.MultiHeadMask([one] * heads), head_shards=1,
                q_seq_shards=1, block_sizes=bs, interpret=interpret)
            return lambda q, k, v: jax.vmap(kernel)(
                (q * scale).astype(q.dtype), k, v)
        kernel = sk.make_splash_mqa(
            sm.MultiHeadMask([one] * group), head_shards=1, q_seq_shards=1,
            block_sizes=bs, interpret=interpret)

    def grouped(q, k, v):   # a KV head and its group of query heads a call
        b, h = q.shape[:2]
        q = (q * scale).astype(q.dtype).reshape(
            (b, h // group, group) + q.shape[2:])
        return jax.vmap(jax.vmap(kernel))(q, k, v).reshape(
            (b, h) + q.shape[3:])

    return grouped


def programs(geo: Geometry, shape: dict, interpret: bool):
    """(forward alone, gradient of a weighted sum of the output in q, k, v):
    both jitted, both of (q, k, v, w)."""
    attn = attention(geo, shape, interpret)
    body = jax.checkpoint(attn) if shape["remat"] else attn

    def loss(q, k, v, w):
        return jnp.sum(body(q, k, v).astype(jnp.float32)
                       * w.astype(jnp.float32))

    return (jax.jit(lambda q, k, v, w: attn(q, k, v)),
            jax.jit(jax.grad(loss, (0, 1, 2))))


def reference_grads(shape: dict):
    """The same gradient from a float32 materialized attention."""
    t = shape["t"]
    heads = shape.get("heads", HEADS)
    group = heads // shape.get("kv_heads", heads)

    def loss(q, k, v, w):
        q, k, v, w = (x.astype(jnp.float32) for x in (q, k, v, w))
        k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
        if shape["causal"]:
            seen = jnp.tril(jnp.ones((t, t), bool))
            if shape.get("window"):
                seen = seen & ~jnp.tril(seen, -shape["window"])
            s = jnp.where(seen, s, -jnp.inf)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
        return jnp.sum(out * w)

    def grads(q, k, v, w):
        with jax.default_matmul_precision("highest"):
            return jax.grad(loss, (0, 1, 2))(q, k, v, w)

    return jax.jit(grads)


def inputs(shape: dict, seed: int, sharding=None):
    heads = shape.get("heads", HEADS)
    head = shape.get("head", HEAD)
    dims = [(shape["rows"], h, shape["t"], d)                   # q k v w
            for h, d in zip((heads,) + (shape.get("kv_heads", heads),) * 2
                            + (heads,),
                            (head,) * 2 + (shape.get("v_head", head),) * 2)]
    if sharding is not None:    # a described chip holds no array
        return [jax.ShapeDtypeStruct(d, jnp.bfloat16, sharding=sharding)
                for d in dims]
    return [jax.random.normal(k, d, jnp.float32).astype(jnp.bfloat16)
            for k, d in zip(jax.random.split(jax.random.PRNGKey(seed), 4),
                            dims)]


def stage_candidates(shape: dict, blocks) -> dict:
    """The three one-kernel sweeps, each varied around 1024 blocks (the
    largest block in rehearsal): a kernel's fit and time do not depend on
    its neighbours' blocks, each is its own custom call."""
    t = shape["t"]
    mid = min(blocks[len(blocks) // 2], t)
    base = (mid, mid, mid)
    tr = triples(blocks, t)
    pairs = [(q, kv) for q, kv in itertools.product(blocks, repeat=2)
             if max(q, kv) <= t]
    return {
        "fwd": [Geometry("splash", f, base, (mid, mid)) for f in tr],
        "dkv_split": [Geometry("splash", base, d, (mid, mid)) for d in tr],
        "dkv_fused": [Geometry("splash", base, d, None) for d in tr],
        "dq": [Geometry("splash", base, base, p) for p in pairs],
    }


def today(shape: dict, blocks) -> list:
    """What the tree before ISSUE 31 built, and the stock flash kernel."""
    t, big, mid = shape["t"], blocks[-1], blocks[len(blocks) // 2]
    kv = big if t % big == 0 else mid
    out = [Geometry("splash", (min(mid, t), kv, kv), (mid,) * 3, (mid, mid))]
    return out + [Geometry("flash", (b,) * 3, (b,) * 3, None)
                  for b in blocks[:2] if b <= t]


def words(e: Exception) -> str:
    text = str(e).replace("\n", " ")
    at = text.find("RESOURCE_EXHAUSTED")
    return (text[at:] if at >= 0 else text)[:400]


def run_fit(args) -> dict:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    out = {}
    for name in args.shapes:
        shape = SHAPES[name]
        xs = inputs(shape, 0, chip)
        stages = stage_candidates(shape, BLOCKS)
        stages["today"] = today(shape, BLOCKS)
        for stage, geos in stages.items():
            for geo in geos:
                fwd, step = programs(geo, shape, False)
                prog = fwd if stage == "fwd" else step
                t0 = time.time()
                try:
                    mem = prog.lower(*xs).compile().memory_analysis()
                    rec = {"fits": True,
                           "temp_bytes": int(mem.temp_size_in_bytes)}
                except Exception as e:      # the compiler's refusal, kept
                    rec = {"fits": False, "words": words(e)}
                rec["compile_s"] = round(time.time() - t0, 2)
                out[f"{name}|{stage}|{geo.id}"] = rec
                print(name, stage, geo.id, rec, flush=True)
    return out


def ms_per_call(fn, xs, calls: int, repeats: int = 3) -> float:
    """Least of ``repeats`` timings of ``calls`` back-to-back calls: the
    device queue stays full, so wall time over calls is device time."""
    jax.block_until_ready(fn(*xs))
    jax.block_until_ready(fn(*xs))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*xs)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls * 1e3)
    return best


def run_time(args) -> dict:
    fit = json.load(open(args.fit)) if args.fit else {}
    shapes = REHEARSAL if args.rehearse else SHAPES
    blocks = REHEARSAL_BLOCKS if args.rehearse else BLOCKS
    calls = 2 if args.rehearse else 30
    out = {"device": jax.devices()[0].device_kind, "rows": {}}
    for name in args.shapes:
        shape = shapes[name]
        xs = inputs(shape, 0)

        def measure(stage, geo):
            key = f"{name}|{stage}|{geo.id}"
            if fit and not fit.get(key, {"fits": True})["fits"]:
                out["rows"][key] = {"skipped": fit[key]["words"]}
                return None
            try:
                fwd, step = programs(geo, shape, args.rehearse
                                     and geo.kernel == "splash")
                rec = {"fwd_ms": ms_per_call(fwd, xs, calls)}
                if stage != "fwd":
                    rec["step_ms"] = ms_per_call(step, xs, calls)
            except Exception as e:
                rec = {"failed": words(e)}
            out["rows"][key] = rec
            print(key, rec, flush=True)
            return rec.get("fwd_ms" if stage == "fwd" else "step_ms")

        def best(stage, geos):
            timed = [(measure(stage, g), g) for g in geos]
            timed = [(ms, g) for ms, g in timed if ms is not None]
            return min(timed, key=lambda x: x[0])[1]

        if args.geometry:       # these alone, no sweep
            for text in args.geometry:
                measure("final", parse(text))
            continue
        stages = stage_candidates(shape, blocks)
        fwd = best("fwd", stages["fwd"]).fwd
        split = best("dkv_split", stages["dkv_split"]).dkv
        fused = best("dkv_fused", stages["dkv_fused"]).dkv
        dq = best("dq", stages["dq"]).dq
        finals = [Geometry("splash", fwd, fused, None),
                  Geometry("splash", fwd, split, dq)]
        if not args.rehearse:
            finals += today(shape, blocks)
        for geo in finals:
            measure("final", geo)
        out[name] = {"fwd": fwd, "dkv_split": split, "dkv_fused": fused,
                     "dq": dq}
    return out


def run_errors(args) -> dict:
    """Worst error of dq, dk, dv over the seeds, as a share of the largest
    reference value and as a relative L2 norm."""
    shapes = REHEARSAL if args.rehearse else SHAPES
    out = {"device": jax.devices()[0].device_kind, "rows": {}}
    for name in args.shapes:
        shape = shapes[name]
        ref = reference_grads(shape)
        for geo in geometries(args):
            _, step = programs(geo, shape, args.rehearse)
            worst = {}
            for seed in range(args.seeds):
                xs = inputs(shape, 1000 + seed)
                for leaf, got, want in zip(("dq", "dk", "dv"), step(*xs),
                                           ref(*xs)):
                    got = np.asarray(got, np.float32)
                    want = np.asarray(want, np.float32)
                    d = got - want
                    for kind, err in (
                            ("max", np.abs(d).max() / np.abs(want).max()),
                            ("l2", np.linalg.norm(d) / np.linalg.norm(want))):
                        k = f"{leaf}_{kind}"
                        worst[k] = max(worst.get(k, 0.0), float(err))
            out["rows"][f"{name}|{geo.id}"] = worst
            print(name, geo.id, worst, flush=True)
    return out


def geometries(args) -> list:
    """``--geometry``'s, then both backwards of each shape's winners from
    ``--winners`` (the time stage's file)."""
    found = [parse(text) for text in args.geometry]
    if args.winners:
        for won in json.load(open(args.winners)).values():
            if isinstance(won, dict) and "dkv_fused" in won:
                fwd = tuple(won["fwd"])
                found += [Geometry("splash", fwd, tuple(won["dkv_fused"]),
                                   None),
                          Geometry("splash", fwd, tuple(won["dkv_split"]),
                                   tuple(won["dq"]))]
    return list(dict.fromkeys(found))


def parse(text: str) -> Geometry:
    """``fwd_q/kv/c:dkv_q/kv/c:fused`` or ``...:dq_q/kv``."""
    fwd, dkv, tail = text.split(":")
    three = lambda s: tuple(int(x) for x in s.split("/"))
    return Geometry("splash", three(fwd), three(dkv),
                    None if tail == "fused" else three(tail))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("stage", choices=("fit", "time", "errors"))
    ap.add_argument("--out", required=True)
    ap.add_argument("--fit", help="the fit stage's file: skip what it refused")
    ap.add_argument("--shapes", nargs="+", default=["lm", "loop"],
                    choices=sorted(SHAPES))
    ap.add_argument("--geometry", action="append", default=[],
                    help="fwd:dkv:fused or fwd:dkv:dq, blocks as "
                         "q/kv/compute (may repeat): what errors compares; "
                         "time measures these alone, without its sweep")
    ap.add_argument("--winners", help="errors: the time stage's file")
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.stage != "fit" and not args.rehearse and (
            jax.devices()[0].platform != "tpu"):
        sys.exit("attn_sweep: no TPU here; times come from the chip only")
    result = {"fit": run_fit, "time": run_time, "errors": run_errors}[
        args.stage](args)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()

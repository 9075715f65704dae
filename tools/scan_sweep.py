#!/usr/bin/env python3
"""The state-space scan alone on the chip (ISSUE 40): the two forms of
``parallel/ssd.py ssd_chunked`` (``scan_form``: the Pallas kernels and the
``jax.numpy`` specification) at one shape, timed forward, forward keeping
what a backward needs, and forward + backward, and held against the
``jax.numpy`` form on float32 operands at the highest matmul precision.
Nothing a cell runs imports this file.

    chiprun --chips 1 -- python tools/scan_sweep.py --out chiprun_out/scan.json

``--defects <seed>[,<seed>...]``: instead, the cell's own check of the scan
(``benchmark/tests/nemotron3_defects.py scan_readings``: the first
state-space layer's ``ssm_mixer`` and ``ssm_scan`` through the
configuration's ``mixer_errors``, at the published widths on the chip) on
the kernels as they are and with a defect planted IN THE KERNELS, which the
benchmark's own defects (put on ``ssd.jnp``) do not reach:
``state_in_bfloat16`` (the carried states rounded to bfloat16 at every
store of the scratch), ``products_return_bfloat16`` (every product's
float32 sum rounded to bfloat16) and ``sums_in_bfloat16`` (every product a
running sum in bfloat16, a term at a time). The defects live here, not in
the package. Beside them, as what the readings are read against:
``chunked_form`` (no defect: the ``jax.numpy`` form on the chip) and
``chunked_form_apart`` (the same behind an optimization barrier).

The shape is the state-space cell's: ``--rows`` 2 of ``--tokens`` 8,192,
``--heads`` 64 of ``--head`` 64 over ``--groups`` 8, state ``--state`` 128,
chunk ``--chunk`` 128, bfloat16 operands. ``--rehearse``: a tiny shape with
the kernels interpreted on the CPU: a test of the script.

``--taps``: instead, the taps in front of the scan (``causal_conv_silu``,
``taps_form``): the ``jax.numpy`` form and the kernels at each ``(rows of T,
channels)`` block of ``--blocks``, timed forward and forward + backward over
the cell's in-projection but dt, ``[2, 8192, 10240]`` in bfloat16 (X, B, C:
4096, 1024, 1024 columns from 4096; 4 taps), and each held against the
``jax.numpy`` form on the same operands (the outputs and the cotangents of
the projection, the taps and the bias). The fastest block is the answer
``ssd.TAPS_BLOCKS`` records::

    python tools/scan_sweep.py --taps --out <file>.json
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.parallel import ssd


def operands(args, seed: int, dtype):
    """Operands drawn as the cell's are in scale: step sizes log-uniform in
    [1e-3, 1e-1], ``A`` in -[1, 16], the rest standard normal."""
    b, t, h, p, g, n = (args.rows, args.tokens, args.heads, args.head,
                        args.groups, args.state)
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(ks[0], (b, t, h, p)).astype(dtype),
            jnp.exp(jax.random.uniform(ks[1], (b, t, h), jnp.float32,
                                       np.log(1e-3), np.log(1e-1))),
            -jax.random.uniform(ks[2], (h,), jnp.float32, 1.0, 16.0),
            jax.random.normal(ks[3], (b, t, g, n)).astype(dtype),
            jax.random.normal(ks[4], (b, t, g, n)).astype(dtype),
            jax.random.normal(ks[5], (h,)),
            jax.random.normal(ks[6], (b, t, h, p)))


def timed(fn, *xs, repeats: int):
    """Best of three of the mean ms a call over ``repeats`` calls."""
    jax.block_until_ready(fn(*xs))
    best = float("inf")
    for _ in range(3):
        start = time.perf_counter()
        for _ in range(repeats):
            out = fn(*xs)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - start) / repeats * 1e3)
    return best


def off(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.sqrt(jnp.sum(jnp.square(got - want))
                          / jnp.sum(jnp.square(want))))


class RoundedStores:
    """A kernel's scratch whose every store is rounded to bfloat16: a state
    kept in bfloat16."""

    def __init__(self, ref):
        self.ref = ref

    def __getattr__(self, name):
        return getattr(self.ref, name)

    def __getitem__(self, at):
        return self.ref[at]

    def __setitem__(self, at, value):
        self.ref[at] = value.astype(jnp.bfloat16).astype(value.dtype)


def state_in_bfloat16(_reference):
    forward = ssd._forward_kernel

    def kernel(*refs, p):
        return forward(*refs[:-1], RoundedStores(refs[-1]), p=p)

    ssd._forward_kernel = kernel
    return lambda: setattr(ssd, "_forward_kernel", forward)


def products_return_bfloat16(_reference):
    dot = ssd._dot
    # (Mosaic's matmul sums in 32 bits and nothing else: the result is
    # rounded after it, as a product that returns bfloat16 would be)
    ssd._dot = lambda left, right, contract: dot(
        left, right, contract).astype(jnp.bfloat16).astype(jnp.float32)
    return lambda: setattr(ssd, "_dot", dot)


def sums_in_bfloat16(_reference):
    """A RUNNING sum kept in bfloat16, as the benchmark's defect of that
    name has it: one term of the contraction at a time, each product
    rounded and added to a bfloat16 total, which is rounded again."""
    dot, f32, bf16 = ssd._dot, jnp.float32, jnp.bfloat16

    def one_term_at_a_time(left, right, contract):
        at = jax.lax.broadcasted_iota(jnp.int32, left.shape, contract[0])
        result = jax.eval_shape(lambda l, r: dot(l, r, contract), left, right)

        def add(k, total):
            term = dot(jnp.where(at == k, left, jnp.zeros_like(left)), right,
                       contract).astype(bf16)
            return (total.astype(f32) + term.astype(f32)).astype(bf16)

        return jax.lax.fori_loop(0, left.shape[contract[0]], add, jnp.zeros(
            result.shape, bf16)).astype(f32)

    ssd._dot = one_term_at_a_time
    return lambda: setattr(ssd, "_dot", dot)


def chunked_form(_reference, apart: bool = False):
    """No defect: the ``jax.numpy`` form on the chip, as the program before
    ISSUE 40 ran it; ``apart``: behind an optimization barrier, so that XLA
    hands it the operands rounded to bfloat16 as a kernel is handed them,
    whatever it would fuse across the call (kernels and chunked form give
    the same bits on the same operands; the cell's check rounds them inside
    the same program)."""
    form, numpy = ssd.scan_form, ssd._ssd_numpy
    ssd.scan_form = lambda *shapes: {"form": "chunked"}
    if apart:
        ssd._ssd_numpy = lambda *xs: numpy(
            *jax.lax.optimization_barrier(xs[:6]), xs[6])

    def undo():
        ssd.scan_form, ssd._ssd_numpy = form, numpy
    return undo


DEFECTS = {"none": None, "state_in_bfloat16": state_in_bfloat16,
           "products_return_bfloat16": products_return_bfloat16,
           "sums_in_bfloat16": sums_in_bfloat16,
           "chunked_form": chunked_form,
           "chunked_form_apart": lambda ref: chunked_form(ref, apart=True)}


def taps_sweep(args) -> dict:
    """The taps alone: ms and relative L2 against the ``jax.numpy`` form,
    a form and block at a time."""
    parts, start, taps = (4096, 1024, 1024), 4096, 4
    rows, tokens = args.rows, args.tokens
    if args.rehearse:
        parts, start, rows, tokens = (256, 128, 128), 128, 2, 768
    # (the step's projection has dt's 64 columns after C; as a jitted
    # function's argument a width no multiple of 128 is copied before the
    # call, which in the step the projection's own output is not)
    width, bf16 = start + sum(parts), jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    # drawn as the cell's are in scale: the taps as _norm_init draws them,
    # the bias uniform in +-taps ** -0.5
    xs = (jax.random.normal(ks[0], (rows, tokens, width)).astype(bf16),
          jax.random.normal(ks[1], (taps, sum(parts))) * taps ** -0.5,
          jax.random.uniform(ks[2], (sum(parts),), jnp.float32, -1.0, 1.0)
          * taps ** -0.5)
    gs = [jax.random.normal(k, (rows, tokens, p)).astype(bf16)
          for k, p in zip(jax.random.split(ks[3], 3), parts)]

    def passes(form):
        def weighed(*xs_):
            return sum(jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32))
                       for o, g in zip(form(*xs_), gs))
        return {"forward": jax.jit(form),
                "both": jax.jit(jax.grad(weighed, range(3)))}

    forms = {"jnp": lambda *xs_: ssd._taps_numpy(*xs_, parts, start, bf16)}
    for bt, bc in args.blocks:
        forms["%dx%d" % (bt, bc)] = functools.partial(
            lambda *xs_, blocks: ssd.taps_kernels(
                *xs_, parts, start, bf16, blocks, interpret=args.rehearse),
            blocks=(bt, bc))
    report = {"device": jax.devices()[0].device_kind, "rows": rows,
              "tokens": tokens, "parts": parts, "start": start,
              "form_here": ssd.taps_form((rows, tokens, width), taps, parts,
                                         start), "ms": {}, "off": {}}
    names = ("X", "B", "C", "du", "dw", "db")
    want = None
    for label, form in forms.items():
        runs = passes(form)
        report["ms"][label] = {k: timed(fn, *xs, repeats=args.repeats)
                               for k, fn in runs.items()}
        got = (*runs["forward"](*xs), *runs["both"](*xs))
        want = want or got
        report["off"][label] = {k: off(g_, w_) for k, g_, w_ in
                                zip(names, got, want)}
        print(label, json.dumps({k: report[k][label] for k in ("ms", "off")}),
              flush=True)
    return report


def planted(seeds, rehearse: bool, out: str) -> int:
    """The cell's check of the scan under each of ``DEFECTS``."""
    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "benchmark")
    sys.path[:0] = [bench, os.path.join(bench, "readers"),
                    os.path.join(bench, "tests")]
    import nemotron3_defects
    report = {"device": jax.devices()[0].device_kind, "readings": {}}
    for seed in seeds:
        for name, defect in DEFECTS.items():
            found = nemotron3_defects.scan_readings(seed, defect, rehearse)
            report["readings"]["%s seed %d" % (name, seed)] = found
            nemotron3_defects.say("scan %s seed %d" % (name, seed), found)
    return write(report, out)


def main() -> int:
    ap = argparse.ArgumentParser()
    for name, default in (("rows", 2), ("tokens", 8192), ("heads", 64),
                          ("head", 64), ("groups", 8), ("state", 128),
                          ("chunk", 128), ("repeats", 20), ("seed", 0)):
        ap.add_argument("--" + name, type=int, default=default)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--defects", default="")
    ap.add_argument("--taps", action="store_true")
    ap.add_argument("--blocks", nargs="*", default=[
        "256x512", "512x512", "1024x512", "2048x512", "512x256", "1024x256",
        "256x1024", "512x1024", "1024x1024"])
    args = ap.parse_args()
    args.blocks = [tuple(int(v) for v in b.split("x")) for b in args.blocks]
    if not args.rehearse and jax.default_backend() != "tpu":
        print("no TPU here: --rehearse runs the script on the CPU")
        return 2
    if args.defects:
        return planted([int(s) for s in args.defects.split(",")],
                       args.rehearse, args.out)
    if args.rehearse:
        args.rows, args.tokens, args.heads, args.groups = 1, 256, 4, 2
        args.repeats = 1
    if args.taps:
        if args.rehearse:
            args.blocks = [(256, 128)]
        return write(taps_sweep(args), args.out)

    def kernels(*xs):
        return ssd.ssd_kernels(*xs, args.chunk, interpret=args.rehearse)

    def numpy(*xs):
        return ssd._ssd_numpy(*xs, args.chunk)

    def passes(form):
        def weighed(*xs):
            return jnp.sum(form(*xs[:6]) * xs[6])
        return {"forward": jax.jit(lambda *xs: form(*xs[:6])),
                # (what jax.checkpoint runs again: the outputs and what the
                # backward will read: the leaves of jax.vjp's function)
                "forward_kept": jax.jit(
                    lambda *xs: jax.vjp(form, *xs[:6])),
                "both": jax.jit(jax.grad(weighed, range(6)))}

    xs = operands(args, args.seed, jnp.bfloat16)
    report = {"device": jax.devices()[0].device_kind, "shape": vars(args),
              "form_here": ssd.scan_form(xs[0].shape, xs[3].shape,
                                         args.chunk), "ms": {}, "off": {}}
    exact = [v.astype(jnp.float32) for v in xs]
    with jax.default_matmul_precision("highest"):
        want = (passes(numpy)["forward"](*exact), *passes(numpy)["both"](
            *exact))
    names = ("y", "dx", "ddt", "da", "db", "dc", "dd")
    for label, form in (("kernel", kernels), ("chunked", numpy)):
        runs = passes(form)
        report["ms"][label] = {k: timed(fn, *xs, repeats=args.repeats)
                               for k, fn in runs.items()}
        got = (runs["forward"](*xs), *runs["both"](*xs))
        report["off"][label] = {k: off(g_, w_) for k, g_, w_ in
                                zip(names, got, want)}
        print(label, json.dumps({k: report[k][label] for k in ("ms", "off")}),
              flush=True)
    return write(report, args.out)


def write(report: dict, out: str) -> int:
    if out:
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as fh:
            json.dump(report, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

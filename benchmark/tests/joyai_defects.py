"""Wrong models for the checks of ``joyai-spmd-1chip-ep32share-8k``, and the
checks run on them. A defect is put into a copy of the plain reference, so
that the program and the reference differ by it; the program's files are not
touched. On the chip at the published widths, where the bands of
``configs/joyai-llm-flash*.py`` were set::

    chiprun --chips 1 -- python3 benchmark/tests/joyai_defects.py \
        <seed>[,<seed>...] float8 [more defects]

(``none`` for a defect's name reads the checks of the program as it is; a
last argument ``rehearse`` runs the rehearsal's widths instead). ``forward``
in front of the seeds reads the forward pass's check alone (``reference``: no
``step_grad``, whose float32 gradient program takes minutes to build), which
is all that a defect of the forward pass needs; ``unsettled`` leaves the
routers' bias at zero (no ``router_settling_passes``: two and a half minutes
a reading; the limits are set on the settled start, so read ``none`` the same
way beside a defect)::

    ... joyai_defects.py forward unsettled <seed> none float8 ...
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

if __name__ == "__main__":      # as a script the benchmark is not on the path
    _bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [os.path.dirname(_bench), _bench,
                    os.path.join(_bench, "readers")]

import files
from job import Env

CONFIG, TRAFFIC = "joyai-llm-flash", "spmd-1chip-2x8192-remat"


def rounded(dtype):
    """The reference with what its norms, attention, FFNs and heads return
    rounded to ``dtype``: a model computed in that precision."""
    def defect(ref):
        def r(x):
            return x.astype(dtype).astype(x.dtype)
        rms, att, swiglu, head = (ref.rmsnorm, ref.attention, ref.swiglu,
                                  ref.head)
        ref.rmsnorm = lambda x, s: r(rms(x, s))
        ref.attention = lambda *a, **k: r(att(*a, **k))
        ref.swiglu = lambda *a: r(swiglu(*a))
        ref.head = lambda w, h: r(head(w, h))
    return defect


def scale_by_the_unrotated_width(ref):
    """Scores scaled by ``dn ** -0.5`` (128) where the head is ``dn + dr``
    (192) wide."""
    attend = ref.attend

    def scaled(q, k, v, lo):
        dn = v.shape[-1]        # (v heads: as wide as the unrotated part)
        return attend(q * (q.shape[-1] / dn) ** 0.5, k, v, lo)
    ref.attend = scaled


def rotation_on_the_whole_head(ref):
    """Every column of q and k rotated, the unrotated 128 too."""
    def rotated(q, k_nope, k_rope):
        k = jnp.concatenate([k_nope, ref.shared_key(k_rope, q.shape[1])],
                            axis=-1)
        return ref.rope(q), ref.rope(k)
    ref.rotated = rotated


def a_key_a_head(ref):
    """The rotated key part NOT shared: head h reads it moved by h
    columns."""
    def own_keys(k_rope, heads):
        return jnp.stack([jnp.roll(k_rope, h, axis=-1)
                          for h in range(heads)], axis=1)
    ref.shared_key = own_keys


def kv_norm_left_out(ref):
    def kv_latent(x, lw):
        rkv = lw["kv_a_norm"].shape[0]
        kv_a = x @ lw["wkv_a"]
        return kv_a[..., :rkv], kv_a[..., rkv:]
    ref.kv_latent = kv_latent


def q_norm_left_out(ref):
    ref.q_latent = lambda x, lw: x @ lw["wq_a"]


def halves_without_the_permutation(ref):
    """The rotate-half form on columns that stand in the published order
    (pairs side by side): the program's form without the permutation that
    ``configs/joyai-llm-flash.py to_reference`` applies."""
    def rope(x):
        t, half = x.shape[-2], x.shape[-1] // 2
        inv_freq = ref.THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
        angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)
    ref.rope = rope


def mtp_scores_the_next_token(ref):
    """The module scored against ``t_{i+1}``, the main head's own target."""
    ref.mtp_cross_entropy = lambda logits, targets: ref.cross_entropy(
        logits[:, :-1], targets[:, :-1])


def mtp_fed_the_same_token(ref):
    """The module fed ``Emb(t_i)`` in place of ``Emb(t_{i+1})``."""
    mtp_input = ref.mtp_input
    ref.mtp_input = lambda weights, h, tokens, targets: mtp_input(
        weights, h, targets, tokens)


def mtp_after_the_final_norm(ref):
    """``h`` taken AFTER the main stack's final norm."""
    mtp_input = ref.mtp_input
    ref.mtp_input = lambda weights, h, tokens, targets: mtp_input(
        weights, ref.rmsnorm(h, weights["ln_f"]), tokens, targets)


def mtp_weight_one(ref):
    ref.MTP_WEIGHT = 1.0


def embedding_from_the_main_term_alone(ref):
    """The embedding takes no gradient through the module's look-up."""
    mtp_input = ref.mtp_input
    ref.mtp_input = lambda weights, h, tokens, targets: mtp_input(
        {**weights, "embed": jax.lax.stop_gradient(weights["embed"])}, h,
        tokens, targets)


def last_position_not_masked(ref):
    """All T positions of the module scored, the last against the row's
    first target."""
    ref.mtp_cross_entropy = lambda logits, targets: ref.cross_entropy(
        logits, jnp.roll(targets, -1, axis=1))


DEFECTS = {"float8": rounded(jnp.float8_e4m3fn),
           "scale_by_the_unrotated_width": scale_by_the_unrotated_width,
           "rotation_on_the_whole_head": rotation_on_the_whole_head,
           "a_key_a_head": a_key_a_head,
           "kv_norm_left_out": kv_norm_left_out,
           "q_norm_left_out": q_norm_left_out,
           "halves_without_the_permutation": halves_without_the_permutation,
           "mtp_scores_the_next_token": mtp_scores_the_next_token,
           "mtp_fed_the_same_token": mtp_fed_the_same_token,
           "mtp_after_the_final_norm": mtp_after_the_final_norm,
           "mtp_weight_one": mtp_weight_one,
           "embedding_from_the_main_term_alone":
           embedding_from_the_main_term_alone,
           "last_position_not_masked": last_position_not_masked}


def cell_checks(seed: int, defect=None, rehearse: bool = True,
                with_step_grad: bool = True, settled: bool = True):
    """``reference`` and ``step_grad`` as the worker runs them, the
    reference a copy with ``defect`` put into it."""
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(TRAFFIC))
    if not settled:
        traffic = {**traffic, "router_settling_passes": 0, "rehearsal": {
            **traffic["rehearsal"], "router_settling_passes": 0}}
    step_file = os.path.splitext(files.config_path(CONFIG))[0] + ".spmd.py"
    ref = files.reference_module(CONFIG)
    if defect is not None:
        defect(ref)
    job = files.load_module(step_file, "bench_step_under_test").build(
        files.config_module(CONFIG), spec, traffic,
        Env(seed=seed, chips=1, rehearse=rehearse, reference=ref))
    return job.reference_checks(job.init(), with_step_grad)


def readings(checks: dict) -> dict:
    """{limit: (what was read, the limit)} over the checks."""
    found = {}
    for check in checks.values():
        found.update({k: (check["error"][k], check["tolerance"][k])
                      for k in check["tolerance"]})
    return found


def failed(found: dict) -> set:
    """The limits a reading is not under (a nan is under none)."""
    return {k for k, (v, band) in found.items() if not v <= band}


def say(name, found):
    print(name, {k: f"{v:.3g} of {b:.3g}" for k, (v, b) in found.items()},
          "FAILS", sorted(failed(found)), flush=True)


if __name__ == "__main__":
    print(jax.devices()[0].device_kind, flush=True)
    rehearse = sys.argv[-1] == "rehearse"
    args = sys.argv[1:len(sys.argv) - rehearse]
    modes = [a for a in args if a in ("forward", "unsettled")]
    args = args[len(modes):]
    for seed in map(int, args[0].split(",")):
        for name in args[1:]:
            t0 = time.monotonic()
            checks = cell_checks(seed, DEFECTS.get(name), rehearse,
                                 "forward" not in modes,
                                 "unsettled" not in modes)
            say("%s seed %d (%s; %.0f s)" % (
                name, seed, " ".join(modes) or "whole", time.monotonic()
                - t0), readings(checks))
            print(json.dumps({"defect": name, "seed": seed,
                              "checks": checks}, default=float), flush=True)

"""The hybrid linear-attention sparse-expert decoder through the SPMD
product: ``make_train_step`` over a mesh, as ``examples/transformer_lm.py
--mode spmd`` runs it. The job is the conv/attention cell's
(``lfm2-8b-a1b.spmd.py build``: the pool on the host, ``reference`` and
``step_grad``), run as it is on this model's files; what differs is here:
the limits, read on this model, the reference's key heads, and no passes
to settle a selection bias, which this model does not have (its
``router_bias_rate`` is 0, so those passes would leave the bias at 0).
"""

from __future__ import annotations

import dataclasses
import os

import files

# a copy of the module of this file's own: the limits below are set on it
_job = files.load_module(os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "lfm2-8b-a1b.spmd.py"), "bench_step_qwen3next")

# ``step_grad`` as ``lfm2-8b-a1b.spmd.py`` describes it: adamw's first moment
# after the window's OWN program took its first step against 0.1 times the
# reference's float32 gradient of the same rows (the recurrence's pieces,
# every layer, every block of attention rows and the exit recomputed), both
# projected on 8 seeded directions a leaf; the delta-rule layers' leaves and
# the shared expert's gate are leaves like any other. A leaf that is missing
# on one side, or a state the step left unchanged, reads the largest of 8
# standard normal draws: 1.4 to 2.8. Read on the v5e at the published widths
# over twelve seeds (PERF.md section 4): ``first_moment`` 1.58e-1 to
# 4.02e-1, the worst leaf a held expert's of the attention layer on eleven
# (``ewu``, ``ewd`` or ``ewg``: an expert's gradient is a sum over about 320
# tokens, and the step chooses otherwise than the forward-only program for
# some of them, as in the sparse-expert cells, PERF.md section 4) and
# ``gdn_ba`` on the twelfth, the median leaf 6.6e-2 to 9.2e-2. The faults of
# the step read 2.20 (the gradient of one row of the two) and 8.84e-1 (the
# step scoring its experts by sigmoid where the forward-only program scores
# them by softmax). The limit sits between the largest reading and 1 with
# the more room above the reading, and under both faults.
# ``flips_step_vs_forward``, half the difference of the step's counts and
# the forward-only program's over the choices, is reported and has no
# limit: 1.04e-3 to 2.96e-3 over the twelve seeds, and the step scoring by
# sigmoid reads 4.45e-3, too near them for a limit to sit between with room
# on both sides (that fault fails ``first_moment``).
GRAD_TOLERANCE = {"first_moment": 0.75, "bias": 0.0}
_job.GRAD_TOLERANCE = GRAD_TOLERANCE


def build(model, spec, traffic, env):
    cfg = model.transformer_config(spec, traffic, env.rehearse)
    if cfg.router_bias_rate == 0:
        traffic = {**traffic, "router_settling_passes": 0, "rehearsal": {
            **traffic["rehearsal"], "router_settling_passes": 0}}
    return _job.build(model, spec, traffic, dataclasses.replace(
        env, reference=model.with_key_heads(env.reference, cfg)))

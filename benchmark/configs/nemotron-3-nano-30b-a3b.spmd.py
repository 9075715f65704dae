"""The state-space/attention sparse-expert decoder through the SPMD product:
``make_train_step`` over a mesh, as ``examples/transformer_lm.py --mode
spmd`` runs it. The job is the conv/attention cell's
(``lfm2-8b-a1b.spmd.py build``: the pool on the host, the routers' bias
settled before anything is timed, ``reference`` and ``step_grad``), run as it
is on this model's files; what differs is here: the limits, read on this
model, and the reference's groups of B and C.
"""

from __future__ import annotations

import dataclasses
import os

import files

# a copy of the module of this file's own: the limits below are set on it
_job = files.load_module(os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "lfm2-8b-a1b.spmd.py"), "bench_step_nemotron3")

# ``step_grad`` as ``lfm2-8b-a1b.spmd.py`` describes it: adamw's first moment
# after the window's OWN program took its first step against 0.1 times the
# reference's float32 gradient of the same rows (the recurrence's pieces,
# every layer, every block of attention rows and the exit recomputed), both
# projected on 8 seeded directions a leaf; ``ssm_A_log``, ``ssm_dt_bias``,
# ``ssm_D`` and ``ssm_conv_b`` are leaves like any other. A leaf that is
# missing on one side, or a state the step left unchanged, reads the largest
# of 8 standard normal draws: 1.4 to 2.8. Read on the v5e at the published
# widths (PR 39; PERF.md section 4): ``first_moment`` 8.0e-2 to 2.0e-1 over
# twenty seeds (eighteen of them under 1.6e-1), the worst leaf the held
# experts' ``ewu`` or ``ewd`` in most of them (an expert's gradient is a sum
# over 768 tokens, and the step chooses otherwise than the forward-only
# program for 5 or 6 of them: the sparse-expert cell's finding, PERF.md
# section 4), the median leaf 3.8e-2 to 4.9e-2, the scan's own leaves 3e-2
# to 1e-1; the reference in an 8-bit float 3.2 and 2.7, a step that summed
# ONE of its two rows 2.6, a state left as ``init`` made it 3.2, rotation
# switched on 3.3. The limit sits between the largest reading and 1 with the
# more room above the reading: two and a half times above it, half of 1. (It
# was the conv/attention cell's 0.3 while the first thirteen seeds were
# read, and was set to 0.5 FROM them, after they came in; the seven seeds
# since were read against 0.5 and stayed under 1.2e-1.)
# ``flips_step_vs_forward``, half the difference of the step's counts and
# the forward-only program's over the choices: 7.5e-4 to 9.6e-4 over the
# twenty seeds (the conv/attention cell reads 1e-5: there a flipped token
# reaches two more tokens, here every later one of its row). It compares
# two programs of the product, so no wrong REFERENCE moves it; a step that
# routes without the settled selection bias, where the forward-only program
# routes by it, reads 2.8e-1
# (``nemotron3_defects.py step_routes_without_the_bias``). The limit is three
# times the largest reading, a hundredth of that one.
GRAD_TOLERANCE = {"first_moment": 0.5, "bias": 0.0,
                  "flips_step_vs_forward": 3e-3}
_job.GRAD_TOLERANCE = GRAD_TOLERANCE


def build(model, spec, traffic, env):
    cfg = model.transformer_config(spec, traffic, env.rehearse)
    return _job.build(model, spec, traffic, dataclasses.replace(
        env, reference=model.with_groups(env.reference, cfg)))

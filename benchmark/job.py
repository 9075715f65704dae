"""What a configuration's step file hands the harness, and what the
harness hands it. ``configs/<config>.<mode>.py`` has one function::

    build(model, spec, traffic, env) -> Job

``model`` is the module ``configs/<config>.py``, ``spec`` and ``traffic``
the two json files of the cell, ``env`` an :class:`Env`.

A check returns ``{"ok": bool, "error": {...}, "tolerance": {...}}``;
``correct`` needs every ``ok``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Tuple


@dataclass
class Env:
    seed: int
    chips: int              # chips the cell uses, all held by this process
    rehearse: bool          # tiny widths on the CPU: counts, never speeds
    reference: Any          # the module reference/<config>.py


@dataclass
class Job:
    samples_per_step: int   # over all chips; the unit is the config's "sample"
    flops_per_sample: float
    # () -> state: weights and optimizer state on the device from the seed
    init: Callable[[], Any]
    # i -> the input of step i, on the device or on its way there
    batch: Callable[[int], Any]
    # (state, batch) -> (state, loss): enqueues one training step as the
    # user's loop does, waits for nothing
    step: Callable[[Any, Any], Tuple[Any, Any]]
    # state -> {name: check}: the program against the plain reference
    reference_checks: Callable[[Any], dict]
    # state -> (state, check), asked of a job over several chips: one step
    # over the mesh against the same step worked out on one chip
    mesh_check: Optional[Callable[[Any], Tuple[Any, dict]]] = None
    # name -> {"flops", "bytes"} one step of that kernel needs on one chip
    kernel_costs: dict = field(default_factory=dict)
    # (state, batch) -> (state, {name: milliseconds}): one step taken apart
    # with waits between its parts, for spans a free-running loop hides
    probe: Optional[Callable[[Any, Any], Tuple[Any, dict]]] = None
    # leaves whatever world the job joined
    close: Callable[[], None] = lambda: None

"""Compatibility shim: the unified run loop moved to :mod:`repro.sim.api`.

``driver.run`` / ``driver.SimConfig`` remain the stable entry names — tests,
benchmarks and committed reports reference them — but the implementation is
now a registry of composable build/step/collect runners (see
:class:`repro.sim.api.Runner`): ``run()`` is the monolithic recomposition,
and the serving layer (``repro.serve.sim_engine``) consumes the split calls
directly.  New code should import from ``repro.sim.api``.
"""

from __future__ import annotations

from repro.sim.api import (  # noqa: F401
    MAX_STEPS,
    RUNNERS,
    RunHandle,
    Runner,
    SimConfig,
    _auto_levels,
    _build_states,
    _device_list,
    _mix_params,
    get_runner,
    register_runner,
    resolve_kind,
    run,
    validate_config,
)

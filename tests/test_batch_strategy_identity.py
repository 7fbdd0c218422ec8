"""The batch engine and the strategy engine compute the same trajectories.

A block-timestep ensemble sharded over its batch axis
(:func:`repro.sim.ensemble.evolve_ensemble_block` with ``devices``) and a
single run domain-decomposed by one of the distribution strategies
(:func:`repro.sim.ensemble.evolve_strategy_block`) lay the same physics out
on the devices in two different ways.  With the Pallas kernel's
launch-extent-independent j-block sweep (``pallas_interpret`` here) and
shard boundaries on block boundaries, every target row is the same
row-local reduction over the sources in the same order, so each member of
the batch run must equal a solo strategy run of that member bit for bit:
same ``pos`` and ``vel``, same event count.

Recipe: two ``plummer`` members at N = 64 (seeds 1 and 2), gather
compaction, one ``dt_max`` macro step over 4 levels; the batch over 2
devices and each strategy over P = 2 domain shards, in one subprocess with
4 forced host devices that every case reads.  At P = 4 the ring's rotated
source sweep regroups the fp32 sums, so the identity is pinned at P = 2.
"""

import json
import os
import subprocess
import sys

import pytest

from repro.core.strategies import STRATEGIES

HERE = os.path.dirname(os.path.abspath(__file__))

_SCRIPT = r"""
import json
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.core.strategies import STRATEGIES
from repro.sim import ensemble as ens
from repro.sim import scenarios

assert len(jax.devices()) == 4
kw = dict(t_end=0.0625, dt_max=0.0625, n_levels=4, eta=0.02, order=6,
          eps=1e-7, impl="pallas_interpret", compaction="gather")
states = [scenarios.make("plummer", 64, seed=seed) for seed in (1, 2)]
batch, cb = ens.evolve_ensemble_block(states, devices=jax.devices()[:2],
                                      **kw)
for strategy in STRATEGIES:
    members = []
    for i, st in enumerate(states):
        solo, cs = ens.evolve_strategy_block(st, strategy=strategy,
                                             devices=2, **kw)
        members.append({
            leaf: bool(np.array_equal(np.asarray(getattr(batch, leaf))[i],
                                      np.asarray(getattr(solo, leaf))))
            for leaf in ("pos", "vel")})
        members[-1]["events"] = [int(np.asarray(cb.n_events)[i]),
                                 int(cs.n_events)]
    print("RESULT " + json.dumps({"strategy": strategy,
                                  "members": members}))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src")
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    out = {}
    for line in res.stdout.splitlines():
        if line.startswith("RESULT "):
            doc = json.loads(line[len("RESULT "):])
            out[doc["strategy"]] = doc["members"]
    return out


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_batch_engine_equals_strategy_engine(results, strategy):
    members = results[strategy]
    assert len(members) == 2
    for i, m in enumerate(members):
        assert m["pos"] and m["vel"], (strategy, i, m)
        ev_batch, ev_solo = m["events"]
        assert ev_batch == ev_solo and ev_batch > 0, (strategy, i, m)

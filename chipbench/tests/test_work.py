"""The operations per pair of ``work.py`` against the reference's formulas,
counted from their traced primitives."""

import collections

import jax
import jax.numpy as jnp

from chipbench import reference, work

#: arithmetic primitives, each one operation; a ``where`` is traced as a
#: nested jit around one ``select_n`` and counts as one select
ARITH = {"add", "sub", "mul", "div", "sqrt", "rsqrt", "neg", "gt", "ge",
         "lt", "le", "eq", "ne", "max", "min", "select_n"}


def count(fn, n_args):
    jaxpr = jax.make_jaxpr(fn)(*([jnp.float32(1.0)] * n_args)).jaxpr
    kinds = collections.Counter()
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("jit", "pjit"):
            inner = [e.primitive.name for e in eqn.params["jaxpr"].jaxpr.eqns]
            assert "select_n" in inner, inner
            kinds["select_n"] += 1
        else:
            assert name in ARITH, name
            kinds[name] += 1
    return sum(kinds.values())


def test_pass1_ops_per_pair():
    pair = count(lambda *a: reference.pass1_pair(
        jnp, a[0:3], a[3:6], a[6], 1e-7), 7)
    # + displacements dr, dv (6) + one add per summed acc, jerk, pot (7)
    assert pair + 6 + 7 == work.OPS_PASS1


def test_pass2_ops_per_pair():
    pair = count(lambda *a: reference.pass2_pair(
        jnp, a[0:3], a[3:6], a[6:9], a[9], 1e-7), 10)
    # + displacements dr, dv, da (9) + one add per summed snap component (3)
    assert pair + 9 + 3 == work.OPS_PASS2


def test_work_counts_pairs_not_slots():
    peaks = {"vpu_f32_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    secs, bound = work.roofline_seconds(1e9, 100, 65536, peaks)
    assert bound == "vpu"
    assert secs == 1e9 * work.OPS_PER_PAIR / 1e12
    assert work.bytes_moved(65536.0 * 10, 1, 65536) == \
        65536 * (work.SRC1 + work.SRC2) + 10 * (work.TGT1 + work.TGT2)

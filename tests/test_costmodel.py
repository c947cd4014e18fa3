"""Paper Eqs. (1)-(8): algebra identities + simulation counter equality."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import costmodel
from repro.core.costmodel import CostParams
from repro.fl.simulation import FLSimulation


@given(st.integers(min_value=2, max_value=500),
       st.integers(min_value=1, max_value=50),
       st.integers(min_value=1, max_value=10**6),
       st.integers(min_value=1, max_value=16),
       st.integers(min_value=1, max_value=100))
@settings(max_examples=60)
def test_expanded_forms_match(n, e, s, m, b):
    p = CostParams(n=n, e=e, s=s, m=m, b=b)
    assert costmodel.twophase_msg_num(p) == costmodel.expand_eq7(p)
    assert costmodel.twophase_msg_size(p) == costmodel.expand_eq8(p)


@given(st.integers(min_value=8, max_value=256))
@settings(max_examples=20)
def test_two_phase_beats_p2p_at_scale(n):
    """The paper's claim: for m << n the two-phase protocol wins."""
    p = CostParams(n=n, e=15, s=242, m=3, b=10)
    assert costmodel.twophase_msg_size(p) < costmodel.p2p_msg_size(p)


def test_paper_figure_regime():
    """Fig. 12: at n=128, SimpleNN, the reduction is order tens."""
    p = CostParams(n=128, e=15, s=242, m=3, b=10)
    assert costmodel.reduction_factor(p) > 20


@pytest.mark.parametrize("n,m,e,s", [(4, 3, 2, 242), (8, 3, 1, 7380),
                                     (16, 4, 2, 100), (6, 2, 3, 55)])
def test_simulation_counters_equal_equations(n, m, e, s):
    rng = np.random.RandomState(0)
    flats = [jnp.asarray(rng.randn(s).astype(np.float32))
             for _ in range(n)]
    p = CostParams(n=n, e=e, s=s, m=m, b=10)

    sim = FLSimulation(n=n, m=m, seed=1)
    for _ in range(e):
        sim.aggregate_p2p(flats)
    st_ = sim.net.stats("p2p")
    assert st_.msg_num == costmodel.p2p_msg_num(p)
    assert st_.msg_size == costmodel.p2p_msg_size(p)

    sim2 = FLSimulation(n=n, m=m, seed=1)
    sim2.elect_committee()
    for _ in range(e):
        sim2.aggregate_two_phase(flats)
    st1 = sim2.net.stats("phase1")
    st2 = sim2.phase2_stats()
    assert st1.msg_num == costmodel.phase1_msg_num(p)
    assert st1.msg_size == costmodel.phase1_msg_size(p)
    assert st2.msg_num == costmodel.phase2_msg_num(p)
    assert st2.msg_size == costmodel.phase2_msg_size(p)

"""Prop. 1 local certificates: soundness (certified => gap <= eps)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import problems, topology as topo
from repro.core.cola import ColaConfig, build_env, init_state, make_round, \
    run_cola
from repro.core.duality import (block_spectral_norms, gap_report,
                                local_certificates, neighbor_mask,
                                neighborhood_mean)
from repro.core.partition import make_partition
from repro.data import synthetic


@pytest.fixture(scope="module")
def setup():
    x, y, _ = synthetic.regression(150, 48, seed=2, sparsity_solution=0.2)
    prob = problems.lasso(jnp.asarray(x), jnp.asarray(y), 5e-2, box=5.0)
    k = 8
    graph = topo.connected_cycle(k, 2)
    part = make_partition(prob.n, k)
    env = build_env(prob, part)
    w = topo.metropolis_weights(graph)
    return prob, graph, part, env, w


def _run(prob, part, env, w, rounds, kappa=4.0):
    state = init_state(prob, part)
    rnd = make_round(prob, part, ColaConfig(kappa=kappa))
    wj = jnp.asarray(w, jnp.float32)
    act = jnp.ones((part.num_nodes,), jnp.float32)
    for _ in range(rounds):
        state = rnd(state, env, wj, act)
    return state


def test_certificate_soundness(setup):
    """Whenever both local conditions hold for every node, the TRUE
    decentralized duality gap is <= eps (Prop. 1 statement)."""
    prob, graph, part, env, w = setup
    sigma_k = block_spectral_norms(env.a_parts)
    beta_ub = topo.beta(w)
    for rounds in (5, 40, 200, 600):
        state = _run(prob, part, env, w, rounds)
        rep = gap_report(prob, part, state.x_parts, state.v_stack)
        for eps in (1e-1, 1e0, 1e1, 1e2):
            cert = local_certificates(
                prob, part, state.x_parts, state.v_stack, env.a_parts,
                env.gp_parts, env.masks, graph.adjacency, beta_ub, sigma_k,
                eps, prob.l_bound)
            if bool(cert.certified):
                assert float(rep.gap) <= eps + 1e-6, (rounds, eps)


def test_certificate_eventually_fires(setup):
    """After enough rounds the certificate certifies a moderate eps."""
    prob, graph, part, env, w = setup
    sigma_k = block_spectral_norms(env.a_parts)
    beta_ub = topo.beta(w)
    state = _run(prob, part, env, w, 1200, kappa=8.0)
    rep = gap_report(prob, part, state.x_parts, state.v_stack)
    eps = max(10.0 * float(rep.gap), 1e-3)
    cert = local_certificates(
        prob, part, state.x_parts, state.v_stack, env.a_parts, env.gp_parts,
        env.masks, graph.adjacency, beta_ub, sigma_k, eps, prob.l_bound)
    # condition (9) needs the *local* gaps small; with enough optimization it
    # must fire for an eps an order of magnitude above the true gap
    assert bool(cert.certified), (float(rep.gap), eps,
                                  np.asarray(cert.local_gap),
                                  np.asarray(cert.grad_disagreement))


def test_certificate_upper_bound_monotone_in_eps(setup):
    """Certifying eps implies certifying any eps' >= eps."""
    prob, graph, part, env, w = setup
    sigma_k = block_spectral_norms(env.a_parts)
    beta_ub = topo.beta(w)
    state = _run(prob, part, env, w, 300)
    fired = []
    for eps in (1e-2, 1e-1, 1e0, 1e1, 1e2, 1e3):
        cert = local_certificates(
            prob, part, state.x_parts, state.v_stack, env.a_parts,
            env.gp_parts, env.masks, graph.adjacency, beta_ub, sigma_k, eps,
            prob.l_bound)
        fired.append(bool(cert.certified))
    # once true, stays true for larger eps
    first = fired.index(True) if True in fired else len(fired)
    assert all(fired[first:])


def test_block_spectral_norms_cache_short_circuits(setup):
    """The sigma_k cache skips the power iteration; bad shapes are rejected."""
    prob, graph, part, env, w = setup
    sigma = block_spectral_norms(env.a_parts)
    cached = block_spectral_norms(env.a_parts, cache=sigma)
    np.testing.assert_array_equal(np.asarray(cached), np.asarray(sigma))
    with pytest.raises(ValueError, match="cache"):
        block_spectral_norms(env.a_parts, cache=sigma[:-1])


def test_a_repeated_certified_solve_compiles_nothing(setup):
    """The recorder is built anew in every solve; its sigma_k power
    iteration compiles once per shape, not once per solve."""
    prob, graph, _, _, _ = setup
    compiles = []

    def on_event(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    solve = lambda: run_cola(prob, graph, ColaConfig(kappa=4.0), 40,
                             record_every=10, recorder="certificate",
                             eps=1e-3)
    solve()
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        solve()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert not compiles


def test_masked_neighborhood_mean_matches_neighbor_average(setup):
    """The masked formulation averages exactly the values a gossip exchange
    delivers: own gradient + each adjacency neighbor's."""
    prob, graph, part, env, w = setup
    k = graph.num_nodes
    rng = np.random.default_rng(0)
    grads = jnp.asarray(rng.normal(size=(k, prob.d)), jnp.float32)
    mask = neighbor_mask(graph.adjacency, k)
    mean = np.asarray(neighborhood_mean(grads, mask))
    for node in range(k):
        neigh = sorted(set(graph.neighbors(node)) | {node})
        np.testing.assert_allclose(
            mean[node], np.asarray(grads)[neigh].mean(axis=0),
            rtol=1e-5, atol=1e-6)
    # passing the mixing matrix instead of the adjacency uses its support
    mask_w = neighbor_mask(topo.metropolis_weights(graph), k)
    np.testing.assert_array_equal(np.asarray(mask_w), np.asarray(mask))


# ---------------------------------------------------------------------------
# Prop.-1 soundness as a property: certified == True  =>  gap <= eps
# ---------------------------------------------------------------------------

_PROP_TOPOS = {  # name -> builder valid for every sampled K
    "ring": topo.ring,
    "complete": topo.complete,
    "star": topo.star,
    "cycle2": lambda k: topo.connected_cycle(k, 2) if k >= 5 else topo.ring(k),
}


@settings(deadline=None, max_examples=10)
@given(seed=st.integers(0, 10 ** 6), k=st.sampled_from([2, 4, 8]),
       topo_name=st.sampled_from(sorted(_PROP_TOPOS)),
       lam=st.sampled_from([1e-2, 5e-2]),
       eps_scale=st.sampled_from([0.5, 3.0, 30.0]),
       rounds=st.sampled_from([15, 80, 300]))
def test_certificate_soundness_property(seed, k, topo_name, lam, eps_scale,
                                        rounds):
    """Across random problems/topologies/partitions: every recorded row with
    certified == 1 has the TRUE decentralized duality gap <= eps (the
    recorder runs the composed gap+certificate row, so both sides of the
    implication come from the same round's state)."""
    rng = np.random.default_rng(seed)
    n_samples = int(rng.integers(40, 90))
    n_features = int(rng.integers(24, 48))  # K rarely divides n: padding hit
    x, y, _ = synthetic.regression(n_samples, n_features, seed=seed,
                                   sparsity_solution=0.3)
    prob = problems.lasso(jnp.asarray(x), jnp.asarray(y), lam, box=5.0)
    graph = _PROP_TOPOS[topo_name](k)

    probe = run_cola(prob, graph, ColaConfig(kappa=4.0), rounds,
                     record_every=max(rounds // 3, 1))
    eps = max(eps_scale * probe.history["gap"][-1], 1e-3)
    res = run_cola(prob, graph, ColaConfig(kappa=4.0), rounds,
                   record_every=max(rounds // 6, 1),
                   recorder="gap+certificate", eps=eps)
    h = res.history
    for gap, certified in zip(h["gap"], h["certified"]):
        if certified:
            assert gap <= eps + 1e-6, (topo_name, k, eps, gap)
    if h["stop_round"] is not None:  # stopped == last row certified
        assert h["certified"][-1] == 1.0


def test_certificates_sound_under_churn_round(setup):
    """Regression: after a node leaves and the Metropolis weights rebalance,
    evaluating the certificate against the REWEIGHTED W's support (what the
    surviving nodes' gossip exchange actually provides) stays sound."""
    prob, graph, part, env, w = setup
    k = graph.num_nodes
    rng = np.random.default_rng(5)

    def churn(t, _rng):
        active = np.ones(k, dtype=bool)
        if t % 3 == 2:
            active[int(rng.integers(0, k))] = False
        return active

    res = run_cola(prob, graph, ColaConfig(kappa=6.0), 400,
                   record_every=399, active_schedule=churn,
                   leave_mode="freeze", seed=5)
    state = res.state
    rep = gap_report(prob, part, state.x_parts, state.v_stack)
    sigma_k = block_spectral_norms(env.a_parts)
    # the final round's surviving subnetwork: node 2 dropped, W reweighted
    active = np.ones(k, dtype=bool)
    active[2] = False
    w_churn = topo.reweight_for_active(graph, active)
    for eps in (1e-1, 1e0, 1e1, 1e2, 1e3):
        cert = local_certificates(
            prob, part, state.x_parts, state.v_stack, env.a_parts,
            env.gp_parts, env.masks, w_churn, topo.beta(w_churn), sigma_k,
            eps, prob.l_bound)
        if bool(cert.certified):
            assert float(rep.gap) <= eps + 1e-6, eps
    # the reweighted mask really excludes the leaver from its neighbors
    mask = np.asarray(neighbor_mask(w_churn, k))
    assert mask[2].sum() == 1.0  # leaver: self only
    for j in graph.neighbors(2):
        assert mask[j, 2] == 0.0


def test_recorder_certificates_sound_under_churn(setup):
    """The DRIVER path under churn: run_cola with a certificate recorder and
    an active_schedule judges every record round against the reweighted
    exchange (dynamic mask + active-subnetwork beta), and every certified
    row is sound against the true gap recorded in the same row."""
    from repro.core import metrics as metrics_lib

    prob, graph, part, env, w = setup
    k = graph.num_nodes
    eps = 10.0

    def churn(t, rng):
        return rng.random(k) < 0.75

    for executor in ("block", "loop"):
        res = run_cola(prob, graph, ColaConfig(kappa=8.0), 500,
                       record_every=20, recorder="gap+certificate", eps=eps,
                       active_schedule=churn, seed=11, executor=executor)
        h = res.history
        for gap, certified in zip(h["gap"], h["certified"]):
            if certified:
                assert gap <= eps + 1e-6, (executor, gap)
        assert h["stop_round"] is not None, executor  # still certifies
    # the driver really switched the recorder to the dynamic (churn) mode
    rec = metrics_lib.make_recorder(
        "certificate", prob, part, env, graph,
        topo.metropolis_weights(graph), eps)
    assert not rec.dynamic
    assert metrics_lib.first_certificate(metrics_lib.dynamize(rec)).dynamic
    # per-round inputs: dropped node leaves the mask, threshold tightens
    # with the sparser active subnetwork's beta
    active = np.ones(k, dtype=bool)
    active[2] = False
    w_churn = topo.reweight_for_active(graph, active)
    mask, thr = metrics_lib.certificate_round_inputs(rec, w_churn, active)
    assert mask[2].sum() == 1 and not mask[3, 2]
    _, thr_full = metrics_lib.certificate_round_inputs(
        rec, topo.metropolis_weights(graph), np.ones(k, dtype=bool))
    assert thr <= thr_full + 1e-12


# ---------------------------------------------------------------------------
# The attack dichotomy as a property: a lying participant is never silent
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=8)
@given(seed=st.integers(0, 10 ** 6),
       n_byz=st.sampled_from([1, 2]),
       scale=st.sampled_from([5.0, 10.0]),
       start=st.sampled_from([0, 5]))
def test_attack_detected_or_neutralized_property(seed, n_byz, scale, start):
    """Across random Byzantine placements (fraction >= 1/K sign-flip):
    EITHER the undefended run visibly breaks AND the honest-cohort
    certificate trips ``certificate_violated`` — no silent poisoning of a
    run that claims a gap guarantee — OR ``robust="trim"`` neutralizes the
    attack (converges within 2x the clean rounds, certificate sound).
    Adversarial placements (e.g. colluders sharing a neighborhood on a
    small torus) may evade the gate, which is exactly when the detection
    arm of the dichotomy must hold instead. The clean defended run must
    never false-alarm."""
    from repro import attack

    x, y, _ = synthetic.regression(48, 24, seed=0)
    prob = problems.lasso(jnp.asarray(x), jnp.asarray(y), 5e-2, box=5.0)
    k = 16
    graph = topo.torus_2d(4, 4)
    rng = np.random.default_rng(seed)
    nodes = tuple(int(n) for n in
                  rng.choice(k, size=n_byz, replace=False))
    byz = attack.Byzantine(nodes=nodes, mode="sign_flip", scale=scale,
                           start=start)

    def go(robust, atk):
        return run_cola(prob, graph, ColaConfig(kappa=2.0, robust=robust),
                        rounds=600, record_every=20,
                        recorder="gap+certificate", eps=1.0,
                        attacks=([atk] if atk else None)).history

    clean = go("trim", None)
    assert clean["violated_round"] is None, \
        "clean trim run false-alarmed the certificate"
    assert clean["stop_round"] is not None

    undefended = go(None, byz)
    broken_and_detected = (
        undefended["violated_round"] is not None
        and (undefended["stop_round"] is None
             or undefended["stop_round"] >= undefended["violated_round"]))

    trim = go("trim", byz)
    neutralized = (trim["violated_round"] is None
                   and trim["stop_round"] is not None
                   and trim["stop_round"] <= 2 * clean["stop_round"])
    assert broken_and_detected or neutralized, (
        nodes, scale, start,
        undefended["violated_round"], undefended["stop_round"],
        trim["violated_round"], trim["stop_round"])

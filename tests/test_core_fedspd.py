"""Unit + integration tests for the paper's core algorithm (Algorithm 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.clustering import (
    cluster_all_clients,
    clustering_accuracy,
    mixture_coefficients,
)
from repro.core.fedspd import (
    FedSPDConfig,
    _select_rows,
    _write_rows,
    seeded_init,
    final_phase,
    init_state,
    make_round_step,
    personalize,
    select_clusters,
)
from repro.core.gossip import (
    GossipSpec,
    consensus_distance,
    fedspd_weight_matrix,
    mix,
    mix_dense,
    mix_permute,
    round_comm_bytes,
)
from repro.data.synthetic import make_mixture_classification
from repro.graphs.topology import make_graph, ring
from repro.models.smallnets import make_classifier


def _simple_setup(n=8, s=2, m=64, dim=8, seed=0):
    data = make_mixture_classification(
        n_clients=n, n_clusters=s, n_per_client=m, dim=dim, n_classes=4,
        seed=seed,
    )
    key = jax.random.PRNGKey(seed)
    params, apply_fn, loss_fn, pel_fn, acc_fn = make_classifier(
        "mlp", key, data.x.shape[-1], data.n_classes
    )
    return data, loss_fn, pel_fn, acc_fn


def test_select_clusters_distribution():
    key = jax.random.PRNGKey(0)
    u = jnp.array([[0.9, 0.1]] * 2000 + [[0.1, 0.9]] * 2000)
    s = select_clusters(key, u)
    assert s.shape == (4000,)
    # clients with 90% mass on cluster 0 mostly select 0
    frac0 = float(jnp.mean((s[:2000] == 0)))
    frac1 = float(jnp.mean((s[2000:] == 1)))
    assert frac0 > 0.85 and frac1 > 0.85


def test_select_never_picks_zero_mass_cluster():
    key = jax.random.PRNGKey(1)
    u = jnp.array([[1.0, 0.0]] * 512)
    s = select_clusters(key, u)
    assert int(jnp.sum(s)) == 0


def test_weight_matrix_row_stochastic_and_matched():
    g = make_graph("er", 12, 4.0, seed=3)
    spec = GossipSpec.from_graph(g)
    s = jnp.array([0, 1] * 6)
    w = np.asarray(fedspd_weight_matrix(spec, s))
    np.testing.assert_allclose(w.sum(axis=1), 1.0, atol=1e-6)
    # Eq. (1): positive weight only for closed-neighborhood same-selection
    for i in range(12):
        for j in range(12):
            if w[i, j] > 0 and i != j:
                assert g.adj[i, j] == 1.0, "non-neighbor mixed in"
                assert int(s[i]) == int(s[j]), "cluster mismatch mixed in"
    assert np.all(np.diag(w) > 0)  # closed neighborhood includes self


def test_mix_permute_equals_dense():
    """The edge-colored collective_permute schedule computes Eq. (1) exactly."""
    for seed in range(3):
        g = make_graph("er", 10, 4.0, seed=seed)
        spec_d = GossipSpec.from_graph(g, mode="dense")
        spec_p = GossipSpec.from_graph(g, mode="permute")
        key = jax.random.PRNGKey(seed)
        tree = {
            "a": jax.random.normal(key, (10, 5, 3)),
            "b": jax.random.normal(key, (10, 17)),
        }
        s = jax.random.randint(key, (10,), 0, 2)
        out_d = mix_dense(spec_d, tree, s)
        out_p = mix_permute(spec_p, tree, s)
        for k in tree:
            np.testing.assert_allclose(out_d[k], out_p[k], atol=1e-5)


def test_mix_ring_consensus_contracts():
    """Repeated mixing on a connected graph contracts consensus distance."""
    g = ring(8)
    spec = GossipSpec.from_graph(g)
    key = jax.random.PRNGKey(0)
    tree = {"w": jax.random.normal(key, (8, 20))}
    s = jnp.zeros((8,), jnp.int32)  # everyone same cluster
    d0 = float(consensus_distance(tree))
    for _ in range(30):
        tree = mix(spec, tree, s)
    d1 = float(consensus_distance(tree))
    assert d1 < 1e-3 * d0


def test_comm_bytes_point_to_point_less_than_multicast():
    g = make_graph("er", 16, 6.0, seed=0)
    spec = GossipSpec.from_graph(g)
    key = jax.random.PRNGKey(0)
    s = jax.random.randint(key, (16,), 0, 2)
    p2p = float(round_comm_bytes(spec, s, 1000, point_to_point=True))
    multi = float(round_comm_bytes(spec, s, 1000, point_to_point=False))
    assert p2p <= multi
    assert p2p > 0


@pytest.mark.slow
def test_clustering_recovers_ground_truth():
    """With well-separated centers, min-loss labeling recovers provenance."""
    data, loss_fn, pel_fn, acc_fn = _simple_setup(n=6, m=96, seed=1)
    key = jax.random.PRNGKey(0)

    # train an oracle model per cluster on pooled same-cluster data
    from repro.optim.sgd import sgd
    opt = sgd()
    params, apply_fn, loss_fn, pel_fn, acc_fn = make_classifier(
        "mlp", key, data.x.shape[-1], data.n_classes
    )
    oracle = []
    for c in range(data.n_clusters):
        mask = data.z_true.reshape(-1) == c
        x = jnp.asarray(data.x.reshape(-1, data.x.shape[-1])[mask])
        y = jnp.asarray(data.y.reshape(-1)[mask])
        p = params
        st = opt.init(p)
        g = jax.jit(jax.grad(loss_fn))
        for i in range(150):
            p, st = opt.update(g(p, {"x": x, "y": y}), st, p, 0.1)
        oracle.append(p)

    centers = jax.tree.map(lambda *ls: jnp.stack(
        [jnp.stack([l] * data.n_clients) for l in ls]), *oracle)
    batch = {"inputs": jnp.asarray(data.x), "targets": jnp.asarray(data.y)}
    z, u = cluster_all_clients(pel_fn, centers, {
        "x": batch["inputs"], "y": batch["targets"]}, data.n_clusters)
    acc = clustering_accuracy(jnp.asarray(z), jnp.asarray(data.z_true), 2)
    assert float(acc) > 0.9, f"clustering acc {float(acc)}"
    # u sums to one
    np.testing.assert_allclose(np.asarray(u).sum(-1), 1.0, atol=1e-5)


def test_mixture_coefficients():
    z = jnp.array([0, 0, 1, 1, 1, 0, 1, 1])
    u = mixture_coefficients(z, 2)
    np.testing.assert_allclose(np.asarray(u), [3 / 8, 5 / 8], atol=1e-6)


@pytest.mark.parametrize("regime", ["full", "stream"])
def test_round_step_runs_and_preserves_invariants(regime):
    data, loss_fn, pel_fn, acc_fn = _simple_setup(n=6, m=48)
    n, s = 6, 2
    fcfg = FedSPDConfig(n_clients=n, n_clusters=s, tau=2, batch=8,
                        regime=regime)
    g = make_graph("er", n, 3.0, seed=0)
    spec = GossipSpec.from_graph(g)
    key = jax.random.PRNGKey(0)

    def model_init(k):
        p, *_ = make_classifier("mlp", k, data.x.shape[-1], data.n_classes)
        return p

    state = init_state(key, model_init, fcfg, data.points_per_client)
    step = jax.jit(make_round_step(loss_fn, pel_fn, spec, fcfg))
    if regime == "full":
        payload = {"inputs": jnp.asarray(data.x), "targets": jnp.asarray(data.y)}
    else:
        payload = {"x": jnp.asarray(data.x[:, :8]), "y": jnp.asarray(data.y[:, :8])}
    for _ in range(3):
        state, metrics = step(state, payload)
    u = np.asarray(state.u)
    np.testing.assert_allclose(u.sum(-1), 1.0, atol=1e-4)
    assert np.all(u >= 0)
    assert int(state.round) == 3
    assert float(state.comm_bytes) > 0
    assert not any(np.isnan(np.asarray(l)).any()
                   for l in jax.tree.leaves(state.centers))


def test_personalize_is_convex_combination():
    fcfg = FedSPDConfig(n_clients=3, n_clusters=2)
    key = jax.random.PRNGKey(0)

    def model_init(k):
        return {"w": jax.random.normal(k, (4,))}

    state = init_state(key, model_init, fcfg, data_m=1)
    # set u deterministically
    u = jnp.array([[1.0, 0.0], [0.0, 1.0], [0.5, 0.5]])
    state = state._replace(u=u)
    pers = personalize(state)
    c = state.centers["w"]  # (S, N, 4)
    np.testing.assert_allclose(pers["w"][0], c[0, 0], atol=1e-6)
    np.testing.assert_allclose(pers["w"][1], c[1, 1], atol=1e-6)
    np.testing.assert_allclose(
        pers["w"][2], 0.5 * c[0, 2] + 0.5 * c[1, 2], atol=1e-6)


@pytest.mark.slow
def test_fedspd_learns_mixture_end_to_end():
    """Integration: FedSPD (client-seeded warm start, paper Assumption 5.6)
    on separable mixture data reaches high personalized accuracy and
    recovers the mixture coefficients (paper Tables 2-3 behaviour)."""
    data, loss_fn, pel_fn, acc_fn = _simple_setup(n=8, m=96, seed=5)
    data2 = make_mixture_classification(
        n_clients=8, n_clusters=2, n_per_client=96, dim=8, n_classes=4,
        seed=5, noise=0.2,
    )
    n, s = 8, 2
    fcfg = FedSPDConfig(n_clients=n, n_clusters=s, tau=5, batch=16, lr0=0.05,
                        tau_final=10)
    g = make_graph("er", n, 4.0, seed=1)
    spec = GossipSpec.from_graph(g)
    key = jax.random.PRNGKey(2)

    def model_init(k):
        p, *_ = make_classifier("mlp", k, data2.x.shape[-1], data2.n_classes)
        return p

    train = {"inputs": jnp.asarray(data2.x), "targets": jnp.asarray(data2.y)}
    state = seeded_init(key, model_init, fcfg, loss_fn, train)
    step = jax.jit(make_round_step(loss_fn, pel_fn, spec, fcfg))
    for _ in range(40):
        state, metrics = step(state, train)

    personalized = final_phase(state, loss_fn, train, fcfg)
    test = {"x": jnp.asarray(data2.x_test), "y": jnp.asarray(data2.y_test)}
    accs = jax.vmap(acc_fn)(personalized, test)
    mean_acc = float(jnp.mean(accs))
    # single-seed trajectory: the margin absorbs XLA-version float drift,
    # not just sampling noise
    assert mean_acc > 0.7, f"FedSPD acc {mean_acc}"

    # u correlates with ground-truth mixture (up to cluster permutation)
    u = np.asarray(state.u)
    mt = data2.mix_true
    direct = np.abs(u - mt).mean()
    flipped = np.abs(u - mt[:, ::-1]).mean()
    assert min(direct, flipped) < 0.2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [1, 2, 5])
@pytest.mark.parametrize("s_count", [1, 2, 3])
def test_write_rows_matches_scatter(s_count, n, dtype):
    """The plane write-back is bitwise the row scatter it replaces, and
    the row select reads back what it wrote."""
    k_plane, k_rows, k_s = jax.random.split(
        jax.random.PRNGKey(10 * s_count + n), 3)
    plane = jax.random.normal(k_plane, (s_count, n, 37)).astype(dtype)
    rows = jax.random.normal(k_rows, (n, 37), jnp.float32)
    s = jax.random.randint(k_s, (n,), 0, s_count)
    got = _write_rows(plane, s, rows)
    want = plane.at[s, jnp.arange(n)].set(rows.astype(dtype))
    bits = lambda a: np.asarray(a).view(f"u{a.dtype.itemsize}")  # noqa: E731
    assert got.dtype == dtype
    np.testing.assert_array_equal(bits(got), bits(want))
    np.testing.assert_array_equal(bits(_select_rows(got, s)),
                                  bits(rows.astype(dtype)))

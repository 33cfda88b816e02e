"""The plain reference (``benchmark/reference``) against the repository's
PyTorch statement of the mathematics (``tests/oracle_torch.py``), on the
same weights at a tiny size. Skipped where torch is not installed."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")

from benchmark.reference import model, qmix  # noqa: E402

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, os.path.join(REPO, "tests"))
import oracle_torch  # noqa: E402

sys.path.pop(0)

A, N, F, E, H, D, NA = 3, 3, 9, 8, 2, 2, 4     # agents, entities, feats, ...


def _tf(rng, e, depth):
    g = lambda *s: rng.standard_normal(s).astype(np.float32) * 0.3  # noqa
    p = {}
    for i in range(depth):
        p[f"block_{i}"] = {
            "attention": {"tokeys": {"kernel": g(e, H * e)},
                          "toqueries": {"kernel": g(e, H * e)},
                          "tovalues": {"kernel": g(e, H * e)},
                          "unifyheads": {"kernel": g(H * e, e),
                                         "bias": g(e)}},
            "norm1": {"scale": 1 + g(e), "bias": g(e)},
            "ff1": {"kernel": g(e, 4 * e), "bias": g(4 * e)},
            "ff2": {"kernel": g(4 * e, e), "bias": g(e)},
            "norm2": {"scale": 1 + g(e), "bias": g(e)}}
    return p


def _flat(p, prefix=""):
    """Nested flax-style dict → the oracle's flat 'a/b' naming."""
    out = {}
    for k, v in p.items():
        if isinstance(v, dict):
            if set(v) <= {"kernel", "bias"} and "kernel" in v:
                out[prefix + k] = torch.tensor(v["kernel"])
                if "bias" in v:
                    out[prefix + k + "_b"] = torch.tensor(v["bias"])
            elif set(v) == {"scale", "bias"}:
                out[prefix + k + "/scale"] = torch.tensor(v["scale"])
                out[prefix + k + "/bias"] = torch.tensor(v["bias"])
            else:
                out.update(_flat(v, prefix + k + "/"))
    return out


@pytest.fixture(scope="module")
def nets():
    rng = np.random.default_rng(0)
    g = lambda *s: rng.standard_normal(s).astype(np.float32) * 0.3  # noqa
    agent = {"feat_embedding": {"kernel": g(F, E), "bias": g(E)},
             "transformer": _tf(rng, E, D),
             "q_basic": {"kernel": g(E, NA), "bias": g(NA)}}
    mixer = {"feat_embedding": {"kernel": g(8, E), "bias": g(E)},
             "transformer": _tf(rng, E, D),
             "hyper_b2": {"kernel": g(E, 1), "bias": g(1)}}
    return agent, mixer, rng


def test_agent_forward_matches_oracle(nets):
    agent, _, rng = nets
    obs = rng.standard_normal((2, A, N, F)).astype(np.float32)
    hid = rng.standard_normal((2, A, E)).astype(np.float32)
    q, h = model.agent_forward(jax.tree.map(jnp.asarray, agent),
                               jnp.asarray(obs), jnp.asarray(hid), heads=H,
                               depth=D, standard_heads=False)
    tq, th = oracle_torch.agent_forward(
        _flat(agent), torch.tensor(obs.reshape(2, A, N * F)),
        torch.tensor(hid), n_entities=N, feat_dim=F, emb=E, heads=H, depth=D)
    np.testing.assert_allclose(np.asarray(q), tq.numpy(), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(h), th.numpy(), rtol=2e-5,
                               atol=2e-5)


def test_episode_loss_matches_oracle(nets):
    agent, mixer, rng = nets
    b, t = 2, 4
    g = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    rows = g(t + 1, b, A, 8)
    mec = rng.integers(0, 2, (t + 1, b, A))
    mean, std = g(t + 1, b, A, 9) * 0.1, 1 + np.abs(g(t + 1, b, A, 9))
    batch = {
        "rows": rows, "mec": mec, "mean": mean, "std": std,
        "state": g(t + 1, b, A * 8),
        "avail": np.concatenate([np.ones((t + 1, b, A, 1), bool),
                                 rng.random((t + 1, b, A, NA - 1)) < 0.7],
                                -1),
        "actions": np.zeros((t, b, A), np.int64),
        "reward": g(t, b), "terminated": np.zeros((t, b), bool),
        "filled": np.ones((t, b), bool)}
    batch["filled"][-1, 1] = False
    weights = np.asarray([1.0, 0.5], np.float32)
    sizes = dict(n_agents=A, emb=E, heads=H, depth=D, mixer_emb=E,
                 mixer_heads=H, mixer_depth=D, standard_heads=False)
    params = jax.tree.map(jnp.asarray, {"agent": agent, "mixer": mixer})
    target = jax.tree.map(lambda x: x * 0.9, params)
    jb = jax.tree.map(jnp.asarray, batch)
    loss, _ = qmix.episode_loss(params, target, jb, jnp.asarray(weights),
                                sizes=sizes, gamma=0.99)

    obs = np.asarray(model.entity_obs(jb["rows"], jb["mec"], jb["mean"],
                                      jb["std"]))          # (T+1,B,A,A,9)
    bt = lambda x: torch.tensor(np.swapaxes(np.asarray(x), 0, 1))  # noqa
    tb = {"obs": bt(obs.reshape(t + 1, b, A, N * F)), "state": bt(batch["state"]),
          "avail": bt(batch["avail"].astype(np.float32)),
          "actions": bt(batch["actions"]), "reward": bt(batch["reward"]),
          "terminated": bt(batch["terminated"].astype(np.float32)),
          "filled": bt(batch["filled"].astype(np.float32))}
    tt = lambda p: {k: v * 0.9 for k, v in _flat(p).items()}  # noqa: E731
    want = oracle_torch.qmix_episode_loss(
        _flat(agent), _flat(mixer), tt(agent), tt(mixer), tb,
        torch.tensor(weights), gamma=0.99, n_agents=A,
        agent_kw=dict(n_entities=N, feat_dim=F, emb=E, heads=H, depth=D),
        mixer_kw=dict(n_agents=A, n_entities=A, feat_dim=8, emb=E, heads=H,
                      depth=D))
    assert float(loss) == pytest.approx(float(want), rel=1e-4)


def test_adam_undo_takes_a_step_back():
    p = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    g = {"w": jnp.asarray([0.5, -0.1, 2.0])}
    z = jax.tree.map(jnp.zeros_like, p)
    new, mu, nu = qmix.adam_step(p, g, z, z, 0, lr=1e-2, eps=1e-5)
    back = qmix.adam_undo(new, mu, nu, 1, lr=1e-2, eps=1e-5)
    np.testing.assert_allclose(np.asarray(back["w"]), np.asarray(p["w"]),
                               rtol=1e-6)


def test_control_precision_reads_above_bf16():
    """fp8 operands move a contraction several times as far from float32
    as bf16 operands do — what the control stands on."""
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    ref = model.mm("ij,jk->ik", a, b, "f32")
    err = {p: float(jnp.abs(model.mm("ij,jk->ik", a, b, p) - ref).mean())
           for p in ("bf16", "fp8")}
    assert err["fp8"] > 5 * err["bf16"] > 0

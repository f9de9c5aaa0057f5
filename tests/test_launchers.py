"""Launcher integration: train.py / serve.py / train_sgns.py CLIs and
grouped-MoE semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_train_launcher_reduces_loss(tmp_path):
    from repro.launch.train import train
    params, losses = train("qwen1.5-0.5b", reduced=True, steps=25, batch=4,
                           seq=48, lr=3e-3, ckpt_dir=str(tmp_path),
                           ckpt_every=20)
    assert losses[-1] < losses[0]
    from repro.checkpoint import latest_step_path, load_checkpoint
    path = latest_step_path(str(tmp_path))
    assert path is not None
    tree, meta = load_checkpoint(path)
    assert meta["step"] == 25
    assert "params" in tree and "opt" in tree


def test_train_launcher_resume(tmp_path):
    from repro.launch.train import train
    train("smollm-360m", reduced=True, steps=10, batch=2, seq=32, lr=1e-3,
          ckpt_dir=str(tmp_path), ckpt_every=100)
    params, losses = train("smollm-360m", reduced=True, steps=5, batch=2,
                           seq=32, lr=1e-3, ckpt_dir=str(tmp_path),
                           ckpt_every=100, resume=True)
    assert len(losses) > 0 and np.isfinite(losses).all()


def test_decode_llm_launcher_generates():
    from repro.launch.decode_llm import serve
    gen, stats = serve("qwen1.5-0.5b", reduced=True, batch=2, prompt_len=6,
                       new_tokens=8)
    assert gen.shape == (2, 8)
    assert stats["tok_per_s"] > 0
    cfg_vocab = 512
    assert int(jnp.max(gen)) < cfg_vocab


def test_train_sgns_cli(capsys):
    from repro.launch.train_sgns import main
    main(["--strategy", "shuffle", "--workers", "3", "--epochs", "2",
          "--dim", "32", "--vocab", "600", "--sentences", "4000",
          "--merge", "alir_pca"])
    out = capsys.readouterr().out
    assert "alir_pca" in out and "sim=" in out


def test_train_sgns_graph_cli(tmp_path, capsys):
    """``--graph``: an edge list written by ``python -m repro.data.graph``
    trains DeepWalk sub-models through the same loop and merge, and the
    saved table's rows are the nodes in descending degree order."""
    from repro.data.graph import Graph, main as graph_main
    from repro.launch.train_sgns import main
    edges, saved = tmp_path / "edges.npz", tmp_path / "emb.npz"
    graph_main(["--out", str(edges), "--nodes", "600", "--edges", "1500",
                "--max-degree", "60"])
    main(["--graph", str(edges), "--workers", "2", "--rate", "0.01",
          "--epochs", "1", "--dim", "16", "--merge", "alir_pca",
          "--save", str(saved)])
    out = capsys.readouterr().out
    assert "graph 600 nodes 1500 edges" in out and "(600, 16)" in out
    deg = Graph.load(edges).degrees
    from repro.checkpoint import load_checkpoint
    word_ids = np.asarray(load_checkpoint(str(saved))[0]["word_ids"])
    assert sorted(word_ids.tolist()) == list(range(600))
    assert np.all(np.diff(deg[word_ids]) <= 0)


def test_train_sgns_publish_then_serve_cli(tmp_path, capsys):
    """The full production loop at CLI granularity: train with --publish,
    then answer queries (merged and sub-model space) with the serve
    launcher against the artifact directory."""
    from repro.launch.serve import main as serve_main
    from repro.launch.train_sgns import main as train_main
    art = str(tmp_path / "artifacts")
    train_main(["--strategy", "random", "--workers", "2", "--epochs", "1",
                "--dim", "16", "--vocab", "400", "--sentences", "3000",
                "--merge", "concat", "--publish", art])
    out = capsys.readouterr().out
    assert "published 2 incremental table version(s)" in out

    serve_main(["--artifact", art, "--query", "1,2,3,999999"])
    out = capsys.readouterr().out
    assert "artifact v2" in out and "space=merged" in out
    assert "[OOV]" in out                     # 999999 is out of vocab
    assert "stats:" in out

    serve_main(["--artifact", art, "--query", "1,2", "--submodel", "0",
                "--version", "1"])
    out = capsys.readouterr().out
    assert "artifact v1" in out and "space=submodel 0" in out


def test_grouped_moe_matches_ungrouped_with_ample_capacity():
    """With capacity high enough that nothing drops, grouping only
    changes dispatch order — outputs must match the ungrouped form."""
    from repro.models import moe as moe_mod
    key = jax.random.PRNGKey(0)
    E, k, d, f = 8, 2, 32, 64
    p = moe_mod.init_moe(key, d, f, E, k, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, d)) * 0.5
    y1, aux1 = moe_mod.moe_forward(p, x, num_experts=E, top_k=k,
                                   capacity_factor=8.0, groups=1)
    y4, aux4 = moe_mod.moe_forward(p, x, num_experts=E, top_k=k,
                                   capacity_factor=8.0, groups=4)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y4), atol=1e-5)
    np.testing.assert_allclose(float(aux1), float(aux4), rtol=1e-5)


def test_grouped_moe_capacity_is_per_group():
    """Capacity binds per group: with tiny capacity, each group drops its
    own overflow (outputs differ from ungrouped — by design, GShard)."""
    from repro.models import moe as moe_mod
    key = jax.random.PRNGKey(0)
    E, k, d, f = 4, 1, 16, 32
    p = moe_mod.init_moe(key, d, f, E, k, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, d))
    y, _ = moe_mod.moe_forward(p, x, num_experts=E, top_k=k,
                               capacity_factor=0.5, groups=4)
    assert np.isfinite(np.asarray(y)).all()

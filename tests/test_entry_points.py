"""The entry points around the library: the chip smoke, the benchmark CLI
and where they keep JAX's persistent compilation cache."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import rmat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


def _run(args, env_extra=None, cwd=REPO, timeout=300):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=cwd)


_CACHE_PROBE = textwrap.dedent("""
    import sys, jax, jax.numpy as jnp
    from repro.core.compilecache import use_persistent_cache
    print(use_persistent_cache(sys.argv[1]))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(3)).block_until_ready()
""")


def _files(path):
    return [f for _, _, fs in os.walk(path) for f in fs]


def test_persistent_cache_honours_env_dir(tmp_path):
    env_dir, checkout = tmp_path / "env_cache", tmp_path / "checkout"
    checkout.mkdir()
    r = _run(["-c", _CACHE_PROBE, str(checkout)],
             dict(JAX_COMPILATION_CACHE_DIR=str(env_dir)))
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(env_dir)
    assert _files(env_dir)
    assert not (checkout / ".jax_cache").exists()


def test_persistent_cache_defaults_to_checkout(tmp_path):
    r = _run(["-c", _CACHE_PROBE, str(tmp_path)])
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(tmp_path / ".jax_cache")
    assert _files(tmp_path / ".jax_cache")


def test_chip_smoke_refuses_cpu():
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_alone_fails(tmp_path):
    """Without the rest of the repo next to it the smoke cannot pass."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    r = _run([str(lone)], cwd=tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_benchmark_run_exits_nonzero_on_section_error(monkeypatch, capsys):
    from benchmarks import run, table1_graphs
    from repro.core import compilecache

    def boom(**kw):
        raise RuntimeError("section broke")

    monkeypatch.setattr(compilecache, "use_persistent_cache", lambda root: "")
    monkeypatch.setattr(table1_graphs, "run", boom)
    with pytest.raises(SystemExit) as exc:
        run.main(["--only", "table1"])
    assert exc.value.code not in (0, None)
    assert "table1/ERROR,0.0,RuntimeError: section broke" in capsys.readouterr().out


@pytest.mark.parametrize("scale,seed", [(8, 3), (9, 1)])
def test_chip_smoke_references_match_networkx(scale, seed):
    import networkx as nx

    g = rmat(scale, 8, seed=seed)
    G = nx.Graph()
    G.add_nodes_from(range(g.n))
    G.add_edges_from(zip(*(a.tolist() for a in g.coo())))
    assert (chip_smoke.ref_triangles(g.indptr, g.indices)
            == sum(nx.triangles(G).values()) // 3)
    want = np.arange(g.n)
    for comp in nx.connected_components(G):
        want[list(comp)] = min(comp)
    assert np.array_equal(chip_smoke.ref_components(g.indptr, g.indices), want)
    assert np.array_equal(chip_smoke.canonical_labels((g.n - want) * 7), want)
    dist, parent = chip_smoke.ref_bfs(g.indptr, g.indices, 0)
    for v, lvl in nx.single_source_shortest_path_length(G, 0).items():
        assert dist[v] == lvl
        if v:
            assert parent[v] == min(u for u in G[v] if dist[u] == lvl - 1)
    pr = nx.pagerank(G, alpha=0.85, tol=1e-12, max_iter=500)
    got = chip_smoke.ref_pagerank(g.indptr, g.indices, 200)
    assert np.abs(got - np.array([pr[v] for v in range(g.n)])).sum() < 1e-6

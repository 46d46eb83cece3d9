"""The launchers as scripts drive them: ``main(argv)`` in-process, the
compile-cache placement, the interpret-mode choice, and the seed-parallel
mesh step (one seed group per device, scalars the only cross-device
traffic)."""
import math
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.kernels import interpret_default
from repro.launch import cache as cache_mod
from repro.launch import serve as serve_launcher
from repro.launch import train as train_launcher
from repro.perturb.pallas import PallasBackend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = ["--arch", "qwen2-0.5b", "--smoke"]


@pytest.mark.parametrize("platform,interpret", [("cpu", True),
                                                ("tpu", False)])
def test_interpret_default_by_platform(monkeypatch, platform, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert interpret_default() is interpret
    assert PallasBackend().interpret is interpret


def test_pallas_refuses_a_platform_it_cannot_compile_for(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        PallasBackend()
    assert PallasBackend(interpret=True).interpret is True


def test_compile_cache_dir_env_wins_else_fixed_repo_path(monkeypatch,
                                                         tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache_mod.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cache_mod.compile_cache_dir() == os.path.join(REPO, ".jax_cache")


@pytest.fixture
def cache_in_tmp(monkeypatch, tmp_path):
    """Launchers enable the persistent cache: keep it in tmp_path and put
    the process back as it was afterwards."""
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    yield tmp_path / "jc"
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_enable_compile_cache_points_jax_at_it(cache_in_tmp):
    assert cache_mod.enable_compile_cache() == str(cache_in_tmp)
    assert jax.config.jax_compilation_cache_dir == str(cache_in_tmp)


def _train(tmp_path, *extra):
    return train_launcher.main([*SMOKE, "--steps", "2", "--batch", "4",
                                "--seq", "16", "--ckpt-dir",
                                str(tmp_path / "ckpt"), *extra])


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_train_main_returns_its_run(cache_in_tmp, tmp_path, backend):
    run = _train(tmp_path, "--backend", backend)
    assert run.result.steps_run == 2
    assert all(math.isfinite(loss) for _, loss in run.result.losses)
    assert len(run.ledger) == 2
    assert os.path.exists(tmp_path / "ckpt" / "ledger.mzl")
    if backend == "pallas":
        assert run.optimizer.backend.interpret is True      # CPU


def test_serve_main_gathers_agree_on_a_replayed_ledger(cache_in_tmp,
                                                       tmp_path):
    _train(tmp_path, "--backend", "pallas")
    tokens = {}
    for gather in ("xla", "pallas"):
        out = serve_launcher.main([
            *SMOKE, "--ledger", str(tmp_path / "ckpt" / "ledger.mzl"),
            "--requests", "3", "--new-tokens", "4", "--gather", gather])
        assert out.engine.paged
        tokens[gather] = [r.out_ids for r in out.requests]
    assert all(len(t) == 4 for t in tokens["xla"])
    assert tokens["xla"] == tokens["pallas"]


def test_serve_main_refuses_a_missing_ledger(cache_in_tmp, tmp_path):
    with pytest.raises(SystemExit, match="does not exist"):
        serve_launcher.main([*SMOKE, "--ledger", str(tmp_path / "nope")])


_MESH_SCRIPT = textwrap.dedent(r"""
    import os, re
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    from repro import exec as zexec, zo
    from repro.data.pipeline import DataSpec, Pipeline
    from repro.launch.train import group_mesh
    from repro.models import all_archs, bundle
    from repro.tree_utils import tree_max_abs_diff

    cfg = all_archs()["qwen2-0.5b"].smoke_cfg
    b = bundle(cfg)
    params = b.init(jax.random.PRNGKey(0))
    batch = Pipeline(DataSpec("lm", batch=8, seq=16, vocab=cfg.vocab_size,
                              seed=0)).batch(0)
    opt = zo.mezo(lr=1e-3, eps=1e-3, backend=os.environ["BACKEND"])
    mesh_prog = zexec.StepProgram(opt, zexec.seed_parallel(4,
                                                           mesh=group_mesh(4)))
    state = mesh_prog.init(params, seed=0)
    ps, ss, bs = mesh_prog.shardings(params, batch, state)
    step = jax.jit(mesh_prog.step_fn(b.loss_fn()), in_shardings=(ps, ss, bs))
    hlo = step.lower(params, state, batch).compile().as_text()
    colls = re.findall(r"=\s*(\S+)\s+(all-reduce|all-gather|reduce-scatter|"
                       r"all-to-all|collective-permute)(?:-start)?\(", hlo)
    assert colls and all(k == "all-gather" and s.startswith("f32[4]")
                         for s, k in colls), colls
    p_mesh, _, m_mesh = step(jax.device_put(params, ps),
                             jax.device_put(state, ss), batch)
    ref = zexec.StepProgram(opt, zexec.seed_parallel(4))
    p_ref, _, m_ref = jax.jit(ref.step_fn(b.loss_fn()))(
        params, ref.init(params, seed=0), batch)
    assert abs(float(m_mesh["loss"]) - float(m_ref["loss"])) < 1e-5
    d = tree_max_abs_diff(jax.device_get(p_mesh), p_ref)
    assert d < 1e-5, d
    print("MESH_SEED_PARALLEL_OK", len(colls), d)
""")


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_seed_parallel_mesh_runs_one_group_per_device(backend):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu", BACKEND=backend)
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "MESH_SEED_PARALLEL_OK" in out.stdout


_OFF_GROUP_MESH_SCRIPT = textwrap.dedent(r"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro import exec as zexec, zo
    from repro.data.pipeline import DataSpec, Pipeline
    from repro.launch import train as train_launcher
    from repro.models import all_archs, bundle
    from repro.models.config import ShapeCell
    from repro.tree_utils import tree_max_abs_diff

    assert len(jax.devices()) == 8      # before dryrun asks for 512
    from repro.launch.dryrun import _compile_case

    cfg = all_archs()["qwen2-0.5b"].smoke_cfg
    b = bundle(cfg)

    # dryrun's seed_parallel cell on a data x model mesh: not a pure group
    # mesh, so GSPMD lays out the sequential body with θ tensor-parallel
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    prog = zexec.StepProgram(zo.mezo(), zexec.seed_parallel(2, mesh=mesh))
    assert prog._group_mesh_axis() is None
    cell = ShapeCell("train_smoke", 16, 4, "train")
    for backend in ("xla", "pallas"):
        _compile_case(cfg, b, cell, mesh, exec_plan="seed_parallel",
                      n_groups=2, backend=backend)
    print("DRYRUN_TP_MESH_OK")

    # a stateful estimator on the group mesh keeps the sequential body
    params = b.init(jax.random.PRNGKey(0))
    batch = Pipeline(DataSpec("lm", batch=8, seq=16, vocab=cfg.vocab_size,
                              seed=0)).batch(0)
    opt = zo.mezo(lr=1e-3, eps=1e-3, estimator="one_point")
    mesh_prog = zexec.StepProgram(
        opt, zexec.seed_parallel(4, mesh=train_launcher.group_mesh(4)))
    assert mesh_prog._group_mesh_axis() == "data"
    state = mesh_prog.init(params, seed=0)
    ps, ss, bs = mesh_prog.shardings(params, batch, state)
    p_mesh, _, m_mesh = jax.jit(mesh_prog.step_fn(b.loss_fn()),
                                in_shardings=(ps, ss, bs))(
        jax.device_put(params, ps), jax.device_put(state, ss), batch)
    ref = zexec.StepProgram(opt, zexec.seed_parallel(4))
    p_ref, _, m_ref = jax.jit(ref.step_fn(b.loss_fn()))(
        params, ref.init(params, seed=0), batch)
    assert abs(float(m_mesh["loss"]) - float(m_ref["loss"])) < 1e-5
    d = tree_max_abs_diff(jax.device_get(p_mesh), p_ref)
    assert d < 1e-5, d
    print("STATEFUL_ON_GROUP_MESH_OK", d)

    # more groups than devices: the groups run in turn, with no mesh
    assert train_launcher.group_mesh(16) is None
    run = train_launcher.main(["--arch", "qwen2-0.5b", "--smoke",
                               "--steps", "1", "--batch", "16", "--seq", "16",
                               "--exec-plan", "seed_parallel",
                               "--n-groups", "16"])
    assert run.optimizer.plan.mesh is None
    assert run.result.steps_run == 1
    print("MORE_GROUPS_THAN_DEVICES_OK")
""")


def test_seed_parallel_off_the_group_mesh_keeps_the_sequential_body(
        tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    out = subprocess.run([sys.executable, "-c", _OFF_GROUP_MESH_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    for marker in ("DRYRUN_TP_MESH_OK", "STATEFUL_ON_GROUP_MESH_OK",
                   "MORE_GROUPS_THAN_DEVICES_OK"):
        assert marker in out.stdout, (marker, out.stdout[-2000:])

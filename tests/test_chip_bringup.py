"""What ISSUE 21 put between the program and the chip, checked on the CPU:
one process per chip from the launcher's worker-env builders, the one
compile-cache function, and chip_smoke.py's control flow (its rehearsal,
its device gate, and that a failed phase fails the run). None of this
proves the chip; ``python chip_smoke.py`` through the chip tool does."""

import json
import os
import subprocess
import sys

import pytest

from horovod_tpu.common import env as env_mod
from horovod_tpu.elastic.launcher import make_elastic_worker_env
from horovod_tpu.runner import launch
from horovod_tpu.runner.hosts import HostInfo, get_host_assignments

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


# -- one process per chip ---------------------------------------------------

def _static(slot, base_env, tpu_chips):
    return launch.make_worker_env(slot, launch.COORDINATOR_VIA_RENDEZVOUS,
                                  "127.0.0.1", 1234, base_env,
                                  tpu_chips=tpu_chips)


def _agents(slot, base_env, tpu_chips):
    # launch_via_task_agents' builder call: the caller's explicit env or {}
    return launch.make_worker_env(slot, launch.COORDINATOR_VIA_RENDEZVOUS,
                                  "127.0.0.1", 1234, base_env or {},
                                  tpu_chips=tpu_chips)


def _elastic(slot, base_env, tpu_chips):
    return make_elastic_worker_env(slot, "127.0.0.1", 1234, base_env,
                                   tpu_chips=tpu_chips)


BUILDERS = pytest.mark.parametrize("build", [_static, _agents, _elastic],
                                   ids=["static", "task_agents", "elastic"])


def _tpu_vars(env):
    return {k: v for k, v in env.items()
            if k.startswith("TPU_") or k == "CLOUD_TPU_TASK_ID"}


@BUILDERS
def test_tpu_host_gives_each_local_rank_its_own_chip(build):
    slots = get_host_assignments([HostInfo("localhost", 4)], 4, 4)
    envs = [_tpu_vars(build(s, {}, 4)) for s in slots]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
    # one world: the same grid and the same address list on every worker,
    # and each worker listens on its own entry of that list
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
    addresses = {e["TPU_PROCESS_ADDRESSES"] for e in envs}
    assert len(addresses) == 1
    ports = [a.rsplit(":", 1)[1] for a in addresses.pop().split(",")]
    assert len(set(ports)) == 4
    assert [e["TPU_PROCESS_PORT"] for e in envs] == ports


@BUILDERS
def test_cpu_world_gets_no_tpu_variable_and_no_cache(build):
    slots = get_host_assignments([HostInfo("localhost", 4)], 4, 4)
    for slot in slots:
        # pinned off the TPU, even on a host that has chips
        env = build(slot, {"JAX_PLATFORMS": "cpu"}, 4)
        assert _tpu_vars(env) == {}
        assert env_mod.JAX_COMPILATION_CACHE_DIR not in env
        # no chips on the host: nothing to divide
        assert _tpu_vars(build(slot, {}, 0)) == {}


@BUILDERS
def test_single_local_process_owns_every_chip(build):
    slot, = get_host_assignments([HostInfo("localhost", 1)], 1, 1)
    assert _tpu_vars(build(slot, {}, 4)) == {}


def test_binding_refuses_what_it_cannot_place():
    slots = get_host_assignments([HostInfo("localhost", 2)], 2, 2)
    with pytest.raises(ValueError, match="one process per chip"):
        launch.tpu_chip_binding(slots[0], 4)


@pytest.fixture
def four_chip_host(monkeypatch, tmp_path):
    """This host reports four TPU chips and the world is not pinned to the
    CPU; returns (worker command, the file a started worker would leave)."""
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    marker = tmp_path / "a_worker_ran"
    return [sys.executable, "-c",
            f"open({str(marker)!r}, 'w').close()"], marker


def test_launcher_fails_when_it_cannot_place_a_slot(four_chip_host):
    """-np 2 on a four-chip host: the refusal must be the launcher's own
    failure (it once died in the worker threads and the launcher exited 0
    having started nothing)."""
    command, marker = four_chip_host
    with pytest.raises(ValueError, match="one process per chip"):
        launch.main(["-np", "2"] + command)
    assert not marker.exists()


def test_elastic_launcher_fails_when_it_cannot_place_a_slot(four_chip_host):
    command, marker = four_chip_host
    assert launch.main(["-np", "2", "--min-np", "2", "-H", "localhost:2"]
                       + command) == 1
    assert not marker.exists()


def test_task_agent_launch_fails_before_any_agent_starts(four_chip_host):
    from horovod_tpu.runner.service import TaskService, make_secret_key
    command, marker = four_chip_host
    key = make_secret_key()
    agents = [TaskService(key, addr=("127.0.0.1", 0)) for _ in range(2)]
    for a in agents:
        a.start()
    try:
        with pytest.raises(ValueError, match="one process per chip"):
            launch.launch_via_task_agents(
                [f"127.0.0.1:{a.port}" for a in agents], key, np=2,
                command=command, base_env={}, timeout=30)
    finally:
        for a in agents:
            a.stop()
    assert not marker.exists()


def test_slot_that_never_ran_fails_the_launch(monkeypatch):
    """A worker thread that dies before its worker ran leaves no exit code;
    that is a failed worker, not a success."""
    def execute(cmd, env=None, index=None, events=None):
        if index == 1:
            raise OSError("could not start")
        return 0
    monkeypatch.setattr(launch.safe_shell_exec, "execute", execute)
    monkeypatch.setattr(launch.threading, "excepthook", lambda args: None)
    with pytest.raises(RuntimeError, match=r"\{1: None\}"):
        launch.launch_static([HostInfo("localhost", 2)], 2, ["true"],
                             {"JAX_PLATFORMS": "cpu"})


def test_workers_share_one_cache_directory():
    slots = get_host_assignments([HostInfo("localhost", 2)], 2, 2)
    dirs = {_static(s, {}, 0)[env_mod.JAX_COMPILATION_CACHE_DIR]
            for s in slots}
    assert dirs == {os.path.join(REPO, ".jax_cache")}
    placed = _static(slots[0], {env_mod.JAX_COMPILATION_CACHE_DIR: "/x"}, 0)
    assert placed[env_mod.JAX_COMPILATION_CACHE_DIR] == "/x"
    # and a remote worker is started with it
    assert env_mod.JAX_COMPILATION_CACHE_DIR in launch._FORWARDED_ENV


def test_local_tpu_chips_counts_usable_chips(tmp_path):
    """Four chips on the bus, one device node: one chip (the one-chip
    machine of the chip tool looks exactly like this)."""
    for i, (vendor, device) in enumerate([("0x1ae0", "0x0063")] * 4
                                         + [("0x8086", "0x0063")]):
        d = tmp_path / "pci" / f"0000:00:{i:02x}.0"
        d.mkdir(parents=True)
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(device + "\n")
    vfio = tmp_path / "dev" / "vfio"
    vfio.mkdir(parents=True)
    (vfio / "vfio").write_text("")
    count = lambda: launch.local_tpu_chips(str(tmp_path / "pci"),
                                           str(tmp_path / "dev"))
    assert count() == 0
    (vfio / "3").write_text("")
    assert count() == 1
    for g in "012":
        (vfio / g).write_text("")
    assert count() == 4


def test_launcher_import_touches_no_device():
    """The launcher must not hold the chips its workers need: importing it
    may import jax but must initialise no backend (an unknown platform
    makes any initialisation raise)."""
    env = dict(os.environ, JAX_PLATFORMS="no_such_platform")
    subprocess.run([sys.executable, "-c",
                    "import horovod_tpu.runner.launch"],
                   cwd=REPO, env=env, check=True, timeout=120)


# -- the compile cache --------------------------------------------------------

def test_compile_cache_both_branches(monkeypatch):
    import jax
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(env_mod.JAX_COMPILATION_CACHE_DIR, "/x")
        jax.config.update("jax_compilation_cache_dir", None)
        assert env_mod.use_compile_cache() == "/x"
        # placed from outside: jax reads the variable itself, the function
        # sets nothing
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(env_mod.JAX_COMPILATION_CACHE_DIR)
        fixed = os.path.join(REPO, ".jax_cache")
        assert env_mod.use_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_one_definition_of_the_cache_directory():
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("tests", "chiprun_out", "__pycache__")]
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    if "jax_compilation_cache_dir" in f.read():
                        hits.append(os.path.relpath(
                            os.path.join(root, name), REPO))
    assert hits == [os.path.join("horovod_tpu", "common", "env.py")]


# -- chip_smoke.py ------------------------------------------------------------

def _smoke(*args, env=None):
    return subprocess.run([sys.executable, SMOKE, *args], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def test_smoke_refuses_a_cpu():
    res = _smoke(env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "device gate" in res.stdout


def test_smoke_rehearsal():
    """Every phase at toy widths on four forced CPU devices (about 30 s,
    the phases after the first run side by side). Its four eager workers
    imitate the four-chip host's process numbering: hvd.rank() must be the
    launcher's slot and the world ordered by it where jax's process index
    differs."""
    res = _smoke("--rehearse")
    assert res.returncode == 0, res.stdout[-4000:] + res.stderr[-4000:]
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert last == {"rehearsal": True, "ok": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 4}}


def test_a_failed_phase_fails_the_smoke(monkeypatch, tmp_path, capsys):
    """The parent with stand-in children: every phase passes but one."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    monkeypatch.setattr(chip_smoke, "HERE", str(tmp_path))
    losses = [3.0, 2.0, 1.0]
    files = {
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "versions": {}},
        "spmd": {"data": {"losses": losses}},
        "eager_rank0": {"losses_optimizer": losses, "losses_engine": []},
    }

    def child_cmd(self, name):
        code = "import json, sys\n"
        for stem, body in files.items():
            code += (f"json.dump({body!r}, "
                     f"open({os.path.join(self.out, stem + '.json')!r}, "
                     f"'w'))\n")
        code += f"sys.exit({1 if name == 'kernels' else 0})"
        return [sys.executable, "-c", code]

    monkeypatch.setattr(chip_smoke.Runner, "child_cmd", child_cmd)
    args = chip_smoke.argparse.Namespace(rehearse=False)
    assert chip_smoke.parent(args) == 1
    out = capsys.readouterr().out
    assert "FAILED: ['kernels']" in out and '"ok"' not in out
    files["eager_rank0"]["losses_optimizer"] = [3.0, 2.0, 1.5]
    monkeypatch.setattr(chip_smoke.Runner, "child_cmd",
                        lambda self, name: child_cmd(self, "other"))
    assert chip_smoke.parent(args) == 1        # eager left the SPMD band
    assert "eager follows spmd" in capsys.readouterr().out
    files["eager_rank0"]["losses_optimizer"] = losses
    assert chip_smoke.parent(args) == 0
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1]) == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite",
                               "count": 1}}

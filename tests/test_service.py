"""Task-service RPC tests (reference test/test_service.py: the HMAC socket
services — here the signed JSON-over-HTTP redesign) + NIC discovery."""

import sys
import time
import urllib.error

import pytest

from horovod_tpu.runner.service import (TaskClient, TaskService,
                                        candidate_driver_ips, make_secret_key,
                                        resolve_driver_ip)


@pytest.fixture
def service():
    key = make_secret_key()
    svc = TaskService(key, addr=("127.0.0.1", 0))
    svc.start()
    yield svc, key
    svc.stop()


def _client(svc, key):
    return TaskClient(f"127.0.0.1:{svc.port}", key)


def test_run_and_wait(service):
    svc, key = service
    c = _client(svc, key)
    assert c.run_command([sys.executable, "-c", "print('hi'); exit(7)"]) == \
        {"started": True}
    assert c.wait_for_command_exit_code(timeout=30) == 7


def test_env_passthrough(service):
    svc, key = service
    c = _client(svc, key)
    c.run_command([sys.executable, "-c",
                   "import os, sys; sys.exit(int(os.environ['T_CODE']))"],
                  env={"T_CODE": "5"})
    assert c.wait_for_command_exit_code(timeout=30) == 5


def test_abort(service):
    svc, key = service
    c = _client(svc, key)
    c.run_command([sys.executable, "-c", "import time; time.sleep(60)"])
    time.sleep(0.5)
    assert c.abort_command()["aborted"] is True
    code = c.wait_for_command_exit_code(timeout=30)
    assert code != 0


def test_second_command_rejected_while_running(service):
    svc, key = service
    c = _client(svc, key)
    c.run_command([sys.executable, "-c", "import time; time.sleep(30)"])
    time.sleep(0.3)
    assert c.run_command(["true"])["started"] is False
    c.abort_command()


def test_bad_signature_rejected(service):
    svc, key = service
    bad = TaskClient(f"127.0.0.1:{svc.port}", make_secret_key())
    with pytest.raises(urllib.error.HTTPError) as ei:
        bad.command_exit_code()
    assert ei.value.code == 401
    # the service remains usable with the right key
    assert _client(svc, key).command_exit_code()["running"] is False


def test_unknown_verb_404(service):
    svc, key = service
    c = _client(svc, key)
    with pytest.raises(urllib.error.HTTPError) as ei:
        c._call("no_such_verb", {})
    assert ei.value.code == 404


def test_probe_reachability(service):
    svc, key = service
    c = _client(svc, key)
    # the service binds 127.0.0.1 only, so the 127.0.0.2 loopback alias is
    # refused (an external unreachable IP can't be used here: the sandbox's
    # egress proxy accepts any outbound connect)
    reach = c.probe(["127.0.0.1", "127.0.0.2"], svc.port)
    assert reach == ["127.0.0.1"]


def test_candidate_driver_ips_always_has_fallback():
    cands = candidate_driver_ips()
    assert cands
    assert cands[-1] == "127.0.0.1"


def test_resolve_driver_ip_intersection(service):
    svc, key = service
    c = _client(svc, key)
    # with a real probe against our own service port, loopback is always in
    # the intersection
    ip = resolve_driver_ip([c], svc.port)
    assert ip in candidate_driver_ips()


def test_resolve_driver_ip_no_agreement():
    class FakeClient:
        def probe(self, addresses, port):
            return []
    with pytest.raises(RuntimeError, match="reachable by every worker"):
        resolve_driver_ip([FakeClient()], 1234)


@pytest.mark.integration
def test_launch_via_task_agents_end_to_end(tmp_path):
    """Two local task agents (standing in for two hosts) run a real
    2-process collective job dispatched through the signed RPC channel —
    the reference's task-server launch flow (driver_service.py:48 +
    task_service RunCommand) without ssh."""
    import os
    from horovod_tpu.runner.launch import launch_via_task_agents

    key = make_secret_key()
    # distinct hostnames so the rendezvous slots don't collide
    a0 = TaskService(key, addr=("127.0.0.1", 0)); a0.start()
    a1 = TaskService(key, addr=("127.0.0.1", 0)); a1.start()
    out = tmp_path / "out"
    out.mkdir()
    script = tmp_path / "w.py"
    script.write_text(
        "import os, json\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "import horovod_tpu as hvd\n"
        "hvd.init()\n"
        "v = np.asarray(hvd.allreduce(np.ones(2), name='t', op=hvd.Sum))\n"
        "p = os.path.join(os.environ['T_OUT'], f'r{hvd.rank()}.json')\n"
        "json.dump({'sum': float(v[0]), 'size': hvd.size()}, open(p, 'w'))\n"
        "hvd.shutdown()\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_STALL_CHECK_DISABLE": "1",
        "T_OUT": str(out),
    }
    try:
        launch_via_task_agents(
            [f"127.0.0.1:{a0.port}", f"localhost:{a1.port}"], key, np=2,
            command=[sys.executable, str(script)], base_env=env, timeout=120)
    finally:
        a0.stop()
        a1.stop()
    import json
    results = [json.load(open(out / f"r{r}.json")) for r in range(2)]
    assert all(r == {"sum": 2.0, "size": 2} for r in results), results


def test_replayed_request_to_other_verb_rejected(service):
    """The MAC binds the verb: a captured signature for one verb cannot be
    re-sent to another (review r2 security finding)."""
    import json as _json
    import time as _time
    import urllib.request
    from horovod_tpu.runner.service import SIG_HEADER, TS_HEADER, _sign
    svc, key = service
    body = _json.dumps({}).encode()
    ts = repr(_time.time())
    sig = _sign(key, "command_exit_code", ts, body)
    req = urllib.request.Request(
        f"http://127.0.0.1:{svc.port}/abort_command", data=body,
        method="POST", headers={SIG_HEADER: sig, TS_HEADER: ts})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=5)
    assert ei.value.code == 401


def test_stale_timestamp_rejected(service):
    import json as _json
    import urllib.request
    from horovod_tpu.runner.service import SIG_HEADER, TS_HEADER, _sign
    svc, key = service
    body = _json.dumps({}).encode()
    ts = repr(1.0)  # 1970
    sig = _sign(key, "command_exit_code", ts, body)
    req = urllib.request.Request(
        f"http://127.0.0.1:{svc.port}/command_exit_code", data=body,
        method="POST", headers={SIG_HEADER: sig, TS_HEADER: ts})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=5)
    assert ei.value.code == 401


def test_launch_error_surfaced(service):
    """A nonexistent binary is an immediate, attributable error, not a
    timeout (review r2 finding)."""
    svc, key = service
    c = _client(svc, key)
    c.run_command(["/no/such/binary-xyz"])
    with pytest.raises(RuntimeError, match="failed to launch"):
        c.wait_for_command_exit_code(timeout=20)


def test_same_host_agents_get_distinct_local_ranks(tmp_path):
    """Two agents on one hostname must become local ranks 0 and 1, not two
    colliding (host, 0) slots (review r2 finding)."""
    import os
    from horovod_tpu.runner.launch import launch_via_task_agents
    key = make_secret_key()
    a0 = TaskService(key, addr=("127.0.0.1", 0)); a0.start()
    a1 = TaskService(key, addr=("127.0.0.1", 0)); a1.start()
    out = tmp_path / "o"; out.mkdir()
    script = tmp_path / "w.py"
    script.write_text(
        "import os, json\n"
        "lr = os.environ['HOROVOD_LOCAL_RANK']\n"
        "open(os.path.join(os.environ['T_OUT'], 'lr_' + lr), 'w').write(lr)\n")
    env = {"T_OUT": str(out),
           "PYTHONPATH": os.path.dirname(os.path.dirname(
               os.path.abspath(__file__)))}
    launch_via_task_agents(
        [f"127.0.0.1:{a0.port}", f"127.0.0.1:{a1.port}"], key, np=2,
        command=[sys.executable, str(script)], base_env=env, timeout=60)
    a0.stop(); a1.stop()
    assert sorted(p.name for p in out.iterdir()) == ["lr_0", "lr_1"]


def test_replayed_request_rejected():
    """A verbatim re-send of a captured signed request must be rejected
    inside the freshness window (ADVICE r2 replay finding)."""
    import time
    import urllib.request
    import urllib.error
    from horovod_tpu.runner.service import (TaskService, make_secret_key,
                                            _sign, SIG_HEADER, TS_HEADER)

    key = make_secret_key()
    svc = TaskService(key, addr=("127.0.0.1", 0))
    svc.start()
    try:
        port = svc.port if hasattr(svc, "port") else \
            svc._httpd.server_address[1]
        url = f"http://127.0.0.1:{port}/probe"
        body = b"{}"
        ts = str(time.time())
        sig = _sign(key, "probe", ts, body)

        def send():
            req = urllib.request.Request(url, data=body, method="POST")
            req.add_header(SIG_HEADER, sig)
            req.add_header(TS_HEADER, ts)
            try:
                with urllib.request.urlopen(req, timeout=5) as r:
                    return r.status
            except urllib.error.HTTPError as e:
                return e.code

        first = send()
        replay = send()
        assert first != 401, "legitimate signed request rejected"
        assert replay == 401, "replayed request accepted"
    finally:
        svc.stop()

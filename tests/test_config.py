"""Declarative deployment config (config.py + configs/cluster.toml).

SURVEY §5: the reference configures by editing source (hardcoded address
maps, sampling constants, gate threshold). One TOML must drive every
entrypoint; these tests parse the shipped example, check strictness, check
both servers' CLI config phases, and boot a real single-node cluster +
tutoring node from one generated file.
"""

import argparse
import asyncio
import os
import socket
import textwrap
from unittest import mock

import pytest

from distributed_lms_raft_llm_tpu import config as cfg_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE = os.path.join(REPO, "configs", "cluster.toml")


class _Stop(Exception):
    pass


def _capture_args(module, argv):
    """Run a server module's main() through its argparse+config phase only,
    returning the fully-resolved namespace (engine/event-loop construction
    is cut off)."""
    captured = {}
    real_parse = argparse.ArgumentParser.parse_args

    def capture(self, argv_=None):
        ns = real_parse(self, argv_)
        captured["ns"] = ns
        return ns

    def stop(*a, **kw):
        raise _Stop

    patches = [mock.patch.object(argparse.ArgumentParser, "parse_args",
                                 capture)]
    if hasattr(module, "PagedEngine"):
        patches.append(mock.patch.object(module, "PagedEngine",
                                         side_effect=stop))

    def fake_run(coro):
        coro.close()
        raise _Stop

    patches.append(mock.patch.object(module.asyncio, "run", fake_run))
    for p in patches:
        p.start()
    try:
        module.main(argv)
    except _Stop:
        pass
    finally:
        for p in patches:
            p.stop()
    return captured["ns"]


def test_example_config_parses_to_reference_topology():
    cfg = cfg_lib.load_config(EXAMPLE)
    assert len(cfg.cluster.nodes) == 5                    # 5 LMS servers
    assert cfg.client_servers[0] == "127.0.0.1:50051"
    assert cfg.tutoring.port == 50054                     # reference port
    assert cfg.sampling.temperature == 0.7                # reference sampling
    assert cfg.sampling.top_k == 50
    assert cfg.sampling.repetition_penalty == 1.2
    assert cfg.gate.threshold == 0.6                      # reference gate
    assert cfg.cluster.linearizable_reads is True
    assert cfg.resilience.queue_depth == 64               # bounded admission
    assert cfg.resilience.breaker_failure_threshold == 5


def test_resilience_section_and_client_kwargs(tmp_path):
    f = tmp_path / "r.toml"
    f.write_text(
        "[resilience]\n"
        "llm_timeout_s = 15.0\n"
        "queue_depth = 4\n"
        "breaker_recovery_s = 1.5\n"
        "backoff_max_s = 0.5\n"
    )
    cfg = cfg_lib.load_config(str(f))
    assert cfg.resilience.llm_timeout_s == 15.0
    assert cfg.resilience.queue_depth == 4
    assert cfg.resilience.breaker_recovery_s == 1.5
    kw = cfg_lib.client_kwargs(cfg)
    assert kw["llm_timeout_s"] == 15.0 and kw["backoff_max_s"] == 0.5
    # Unset knobs keep their defaults.
    assert cfg.resilience.deadline_floor_s == 0.25


def test_unknown_keys_rejected(tmp_path):
    bad = tmp_path / "bad.toml"
    bad.write_text("[tutoring]\nmodle = 'gpt2'\n")
    with pytest.raises(ValueError, match="modle"):
        cfg_lib.load_config(str(bad))
    bad.write_text("[tutorng]\nmodel = 'gpt2'\n")
    with pytest.raises(ValueError, match="tutorng"):
        cfg_lib.load_config(str(bad))
    bad.write_text("[resilience]\nqueue_dpeth = 4\n")
    with pytest.raises(ValueError, match="queue_dpeth"):
        cfg_lib.load_config(str(bad))


def test_engine_and_raft_adapters(tmp_path):
    f = tmp_path / "c.toml"
    f.write_text(textwrap.dedent("""
        [cluster]
        election_timeout = 0.3
        heartbeat_interval = 0.05
        [cluster.nodes]
        1 = "127.0.0.1:7001"
        [tutoring]
        model = "tiny"
        quant = "int8"
        kv_quant = true
        [sampling]
        max_new_tokens = 16
        temperature = 0.9
    """))
    cfg = cfg_lib.load_config(str(f))
    ec = cfg_lib.engine_config(cfg)
    assert ec.model == "tiny" and ec.quant == "int8" and ec.kv_quant
    assert ec.sampling.max_new_tokens == 16
    assert ec.sampling.temperature == 0.9
    rc = cfg_lib.raft_config(cfg)
    assert rc.election_timeout_max == 0.3
    assert rc.election_timeout_min == 0.15


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _write_deploy_toml(tmp_path, lms_port, tut_port):
    f = tmp_path / "deploy.toml"
    f.write_text(textwrap.dedent(f"""
        [cluster]
        data_dir = "{tmp_path}/lms"
        election_timeout = 0.3
        heartbeat_interval = 0.05
        [cluster.nodes]
        1 = "127.0.0.1:{lms_port}"
        [tutoring]
        address = "127.0.0.1:{tut_port}"
        model = "tiny"
        kv_quant = true
        [sampling]
        max_new_tokens = 8
    """))
    return f


def test_server_cli_config_phases(tmp_path):
    """Both servers resolve their settings from the file; explicit flags win."""
    from distributed_lms_raft_llm_tpu.serving import lms_server, tutoring_server

    lms_port, tut_port = _free_port(), _free_port()
    f = _write_deploy_toml(tmp_path, lms_port, tut_port)

    # `--jax-platform default` means the TPU and refuses anything else;
    # a CPU run says so.
    cpu = ["--jax-platform", "cpu"]
    targs = _capture_args(tutoring_server, ["--config", str(f), *cpu])
    assert targs.port == tut_port
    assert targs.model == "tiny"
    assert targs.kv_quant and not hasattr(targs, "paged")
    assert targs.max_new_tokens == 8

    # Explicit flag beats the file.
    targs2 = _capture_args(
        tutoring_server,
        ["--config", str(f), "--max-new-tokens", "4", *cpu],
    )
    assert targs2.max_new_tokens == 4

    largs = _capture_args(lms_server, ["--config", str(f), "--id", "1"])
    assert largs.id == 1
    assert largs.port == lms_port
    assert largs.peers == [f"127.0.0.1:{lms_port}"]
    assert largs.tutoring == f"127.0.0.1:{tut_port}"
    assert largs.data_dir == f"{tmp_path}/lms/node1"
    assert largs.election_timeout == 0.3
    assert largs.linearizable_reads is True


def test_cluster_and_tutoring_boot_from_one_file(tmp_path):
    """The done-criterion: LMS node + tutoring node + client all launch from
    one TOML and serve a real register/login."""
    from distributed_lms_raft_llm_tpu.client.client import LMSClient
    from distributed_lms_raft_llm_tpu.engine import PagedEngine
    from distributed_lms_raft_llm_tpu.serving import lms_server, tutoring_server

    lms_port, tut_port = _free_port(), _free_port()
    f = _write_deploy_toml(tmp_path, lms_port, tut_port)
    largs = _capture_args(lms_server, ["--config", str(f), "--id", "1"])

    async def boot():
        cfg = cfg_lib.load_config(str(f))
        engine = PagedEngine(cfg_lib.engine_config(cfg),
                             slots=cfg.tutoring.max_batch)
        tut = await tutoring_server.serve_async(cfg.tutoring.port, engine)
        lms_task = asyncio.get_running_loop().create_task(
            lms_server.serve_async(largs)
        )
        try:
            client = LMSClient(cfg.client_servers, discovery_rounds=30,
                               discovery_backoff_s=0.2)
            loop = asyncio.get_running_loop()
            leader = await loop.run_in_executor(None, client.discover_leader)
            assert leader == f"127.0.0.1:{lms_port}"
            resp = await loop.run_in_executor(
                None, lambda: client.register("cfguser", "pw", "student")
            )
            assert resp.success
            ok = await loop.run_in_executor(
                None, lambda: client.login("cfguser", "pw")
            )
            assert ok
            client.close()
        finally:
            lms_task.cancel()
            try:
                await lms_task
            except (asyncio.CancelledError, Exception):
                pass
            await tut.stop(None)

    asyncio.run(boot())

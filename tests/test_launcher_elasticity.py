"""Launcher + elasticity tests (reference ``tests/unit/launcher/``,
``tests/unit/elasticity/test_elastic.py``)."""

import os
import subprocess
import sys
import textwrap

import pytest

from deepspeed_tpu.elasticity import (
    ElasticAgent,
    ElasticityConfigError,
    ElasticityIncompatibleWorldSize,
    compute_elastic_config,
    get_compatible_chips_v01,
    get_compatible_chips_v02,
    valid_chip_counts,
)
from deepspeed_tpu.launcher import (
    decode_world_info,
    encode_world_info,
    filter_resources,
    parse_hostfile,
    select_runner,
)
from deepspeed_tpu.launcher.launch import build_rank_envs


# ---------------------------------------------------------------- hostfile

def test_parse_hostfile():
    res = parse_hostfile(textwrap.dedent("""\
        # comment
        worker-0 slots=4
        worker-1 slots=8

        worker-2
    """))
    assert list(res.items()) == [("worker-0", 4), ("worker-1", 8),
                                 ("worker-2", 1)]


def test_parse_hostfile_rejects_bad_line():
    with pytest.raises(ValueError):
        parse_hostfile("worker-0 slots=four")
    with pytest.raises(ValueError):
        parse_hostfile("w0 slots=2\nw0 slots=2")


def test_filter_include_exclude():
    res = parse_hostfile("a slots=4\nb slots=4\nc slots=4")
    inc = filter_resources(res, include="a@c:0,1")
    assert dict(inc) == {"a": 4, "c": 2}
    exc = filter_resources(res, exclude="b")
    assert dict(exc) == {"a": 4, "c": 4}
    with pytest.raises(ValueError):
        filter_resources(res, include="a", exclude="b")
    with pytest.raises(ValueError):
        filter_resources(res, include="nope")


def test_world_info_roundtrip():
    res = parse_hostfile("a slots=4\nb slots=2")
    assert decode_world_info(encode_world_info(res)) == {"a": 4, "b": 2}


# ------------------------------------------------------------------ launch

def test_build_rank_envs_per_host():
    world = {"a": 4, "b": 4}
    envs = build_rank_envs(world, node_rank=1, master_addr="a",
                           master_port="29500", proc_per_chip=False)
    assert len(envs) == 1
    assert envs[0]["RANK"] == "1" and envs[0]["WORLD_SIZE"] == "2"
    assert envs[0]["CROSS_RANK"] == "1" and envs[0]["CROSS_SIZE"] == "2"


def test_build_rank_envs_per_chip():
    world = {"a": 2, "b": 3}
    envs = build_rank_envs(world, node_rank=1, master_addr="a",
                           master_port="1", proc_per_chip=True)
    assert [e["RANK"] for e in envs] == ["2", "3", "4"]
    assert all(e["WORLD_SIZE"] == "5" for e in envs)
    assert [e["LOCAL_RANK"] for e in envs] == ["0", "1", "2"]


def test_launch_runs_script_per_rank(tmp_path):
    """End-to-end: launch.py spawns ranks with the right env contract."""
    script = tmp_path / "train.py"
    out = tmp_path / "out"
    script.write_text(textwrap.dedent(f"""\
        import os, sys
        rank = os.environ["RANK"]
        with open(r"{out}" + rank, "w") as fh:
            fh.write(",".join([rank, os.environ["WORLD_SIZE"],
                               os.environ["MASTER_ADDR"], sys.argv[1],
                               sys.argv[-1]]))
    """))
    world = encode_world_info({"localhost": 2})
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         f"--world_info={world}", "--node_rank=0", "--master_addr=127.0.0.1",
         "--master_port=29501", "--proc_per_chip", str(script), "--", "xyz"],
        capture_output=True, text=True, timeout=60,
        cwd="/root/repo", env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr
    assert (tmp_path / "out0").read_text() == "0,2,127.0.0.1,--local_rank=0,xyz"
    assert (tmp_path / "out1").read_text() == "1,2,127.0.0.1,--local_rank=1,xyz"


def test_launch_propagates_child_failure(tmp_path):
    script = tmp_path / "boom.py"
    script.write_text("import sys; sys.exit(3)")
    world = encode_world_info({"localhost": 2})
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         f"--world_info={world}", "--node_rank=0", "--master_addr=x",
         "--master_port=1", "--proc_per_chip", str(script)],
        capture_output=True, timeout=60, cwd="/root/repo")
    assert proc.returncode == 3


def test_proc_per_chip_is_refused_on_a_tpu_host(tmp_path, monkeypatch):
    """Nothing hands each child its own chip, so N processes on a host
    that holds chips would fight over all of them: refuse, start none.
    One slot (one process for the whole host) stays allowed."""
    from deepspeed_tpu.launcher import launch
    marker = tmp_path / "ran"
    script = tmp_path / "child.py"
    script.write_text(f"open(r'{marker}', 'a').write('x')")
    monkeypatch.setattr(launch, "tpu_chips_on_host", lambda: 4)

    def run(slots):
        return launch.main([
            f"--world_info={encode_world_info({'localhost': slots})}",
            "--node_rank=0", "--proc_per_chip", str(script)])
    assert run(4) == 2 and not marker.exists()
    assert run(1) == 0 and marker.read_text() == "x"
    # on a host without chips (the CPU virtual mesh) the flag works
    monkeypatch.setattr(launch, "tpu_chips_on_host", lambda: 0)
    assert run(2) == 0 and marker.read_text() == "xxx"


def test_runner_cmd_construction():
    class Args:
        master_addr = "w0"
        master_port = 29500
        proc_per_chip = False
        user_script = "train.py"
        user_args = ["--foo", "1"]
        tpu_name = "pod"
        tpu_zone = None

    world = encode_world_info({"w0": 4, "w1": 4})
    ssh = select_runner("ssh", Args(), world)
    ssh.add_export("XLA_FLAGS", "--flag")
    cmd = ssh.get_cmd({}, {"w0": 4, "w1": 4})
    joined = " ".join(cmd)
    assert cmd[0] == "/bin/bash" and "ssh" in joined
    assert "--node_rank=0" in joined and "--node_rank=1" in joined
    assert "XLA_FLAGS" in joined

    pdsh = select_runner("pdsh", Args(), world)
    pcmd = pdsh.get_cmd({}, {"w0": 4, "w1": 4})
    assert pcmd[0] == "pdsh" and "w0,w1" in pcmd

    with pytest.raises(ValueError):
        select_runner("bogus", Args(), world)


# -------------------------------------------------------------- elasticity

ELASTIC_CFG = {
    "elasticity": {
        "enabled": True,
        "max_train_batch_size": 2000,
        "micro_batch_sizes": [2, 4, 6],
        "min_gpus": 1,
        "max_gpus": 10000,
        "version": 0.1,
    }
}


def test_valid_chip_counts_math():
    # batch 24, micro 4 -> gas*chips = 6 -> chips in {1,2,3,6}
    assert valid_chip_counts(24, [4], 1, 100) == [1, 2, 3, 6]
    # min/max window applies
    assert valid_chip_counts(24, [4], 2, 3) == [2, 3]


def test_v01_batch_divisible_by_all_valid():
    final, valid = get_compatible_chips_v01([2, 4, 6], 2000)
    assert final <= 2000 and len(valid) >= 30
    for chips in valid:
        assert any(final % (m * chips) == 0 for m in [2, 4, 6]), chips


def test_compute_elastic_config_deterministic():
    a = compute_elastic_config(ELASTIC_CFG)
    b = compute_elastic_config(ELASTIC_CFG)
    assert a == b and len(a) == 2
    # micro batch only returned on request (reference API shape)
    assert len(compute_elastic_config(ELASTIC_CFG, return_microbatch=True)) == 3


def test_candidate_batch_respects_cap():
    # lcm(2,3)=6 exceeds the cap of 5 and must not leak through
    final, valid = get_compatible_chips_v01([2, 3], 5)
    assert final <= 5


def test_compute_elastic_config_world_size_check():
    final, valid, micro = compute_elastic_config(ELASTIC_CFG, world_size=4)
    assert 4 in valid and micro in (2, 4, 6)
    assert final % (micro * 4) == 0
    bad = max(valid) + 1
    while bad in valid:
        bad += 1
    with pytest.raises(ElasticityIncompatibleWorldSize):
        compute_elastic_config(ELASTIC_CFG, world_size=bad)


def test_elastic_config_errors():
    with pytest.raises(ElasticityConfigError):
        compute_elastic_config({})
    with pytest.raises(ElasticityConfigError):
        compute_elastic_config({"elasticity": {"enabled": False}})
    with pytest.raises(ElasticityConfigError):
        compute_elastic_config({"elasticity": {
            "enabled": True, "max_train_batch_size": 100,
            "micro_batch_sizes": [2], "model_parallel_size": 4}})


def test_v02_host_granularity():
    final, valid_dp, micro = get_compatible_chips_v02(
        [2, 4], 1024, current_num_chips=8, chips_per_host=4,
        model_parallel_size=2)
    # dp ranks come in units of chips_per_host/mp = 2
    assert all(v % 2 == 0 for v in valid_dp)
    assert 8 // 2 in valid_dp
    assert micro in (2, 4)
    assert final % (micro * 4) == 0


def test_exclude_validates_slot_indices():
    res = parse_hostfile("a slots=4")
    with pytest.raises(ValueError):
        filter_resources(res, exclude="a:9")


def test_v02_no_world_size_returns_full_valid_set():
    # without a current allocation the degraded fallback must NOT collapse
    # the valid set to num_gpus_per_node
    cfg = {"elasticity": {
        "enabled": True, "max_train_batch_size": 1024,
        "micro_batch_sizes": [2, 4], "min_gpus": 8, "max_gpus": 64,
        "num_gpus_per_node": 4, "version": 0.2}}
    _, valid = compute_elastic_config(cfg)
    assert len(valid) > 1 and all(v >= 2 for v in valid)


def test_v02_min_bound_respected():
    from deepspeed_tpu.elasticity import ElasticityConfigError
    # min_gpus=6 with 4-chip hosts: 1 host (4 chips) violates the minimum
    _, valid_dp, _ = get_compatible_chips_v02(
        [2], 1024, current_num_chips=0, min_chips=6, max_chips=64,
        chips_per_host=4)
    assert all(v * 1 >= 2 for v in valid_dp)  # dp units
    assert min(valid_dp) * 1 >= 8 // 4 * 4 // 4 * 2  # >= 2 hosts worth
    with pytest.raises(ElasticityConfigError):
        get_compatible_chips_v02([2], 1024, current_num_chips=0,
                                 min_chips=1, max_chips=2, chips_per_host=4)


def test_usable_chip_count_respects_mp():
    from deepspeed_tpu.elasticity import usable_chip_count
    cfg = {"elasticity": {
        "enabled": True, "max_train_batch_size": 256,
        "micro_batch_sizes": [2], "min_gpus": 1, "max_gpus": 64,
        "num_gpus_per_node": 4, "model_parallel_size": 2, "version": 0.2}}
    chips = usable_chip_count(cfg, 8)
    assert chips <= 8 and chips % 2 == 0  # whole mp groups only


def test_v02_degraded_fallback():
    # current allocation not in valid set -> keep it, shrink batch
    final, valid_dp, micro = get_compatible_chips_v02(
        [5], 37, current_num_chips=7, chips_per_host=1)
    assert valid_dp == [7]
    assert final == 35 and micro == 5


def test_elastic_agent_rescales_and_resumes():
    calls = []
    avail = iter([8, 8, 6, 5])

    def probe():
        return next(avail)

    def launch(world):
        calls.append(world)
        return 0 if len(calls) >= 3 else 1

    agent = ElasticAgent(ELASTIC_CFG, launch, probe, restart_backoff_s=0.0)
    result = agent.run()
    assert result.exit_code == 0 and result.restarts == 2
    # world sizes tracked the shrinking pod, always from the valid set
    _, valid = compute_elastic_config(ELASTIC_CFG)
    assert all(w in valid for w in result.world_sizes)
    assert result.world_sizes[0] >= result.world_sizes[-1]

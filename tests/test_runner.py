"""Launcher tests (reference pattern: test/single/test_run.py — arg
parsing and launch mechanics as pure unit tests with real subprocesses
on localhost; SURVEY.md §4)."""

import subprocess
import sys

import pytest

from horovod_tpu.runner import check_build_str, parse_args, run


class TestParseArgs:
    def test_defaults(self):
        args = parse_args(["-np", "4", "python", "train.py"])
        assert args.num_proc == 4
        assert args.command == ["python", "train.py"]
        assert not args.check_build

    def test_check_build_flag(self):
        assert parse_args(["--check-build"]).check_build

    def test_version_flag(self, capsys):
        from horovod_tpu.version import __version__

        with pytest.raises(SystemExit) as exc:
            parse_args(["--version"])
        assert exc.value.code == 0
        assert __version__ in capsys.readouterr().out

    def test_elastic_args(self):
        args = parse_args(["-np", "2", "--min-np", "1", "--max-np", "4",
                           "--host-discovery-script", "./d.sh", "x"])
        assert args.min_np == 1 and args.max_np == 4
        assert args.host_discovery_script == "./d.sh"


class TestCheckBuild:
    def test_feature_matrix_contents(self):
        out = check_build_str()
        assert "horovod_tpu v" in out
        assert "jax.distributed" in out
        assert "XLA collectives" in out
        assert "sequence/context parallel" in out

    @pytest.mark.slow
    def test_cli_check_build(self):
        res = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner", "--check-build"],
            capture_output=True, text=True, timeout=120,
        )
        assert res.returncode == 0
        assert "Available controllers" in res.stdout


class TestLocalRun:
    def test_single_process_success(self):
        assert run(1, [sys.executable, "-c", "print('ok')"]) == 0

    def test_failure_propagates(self):
        assert run(1, [sys.executable, "-c", "raise SystemExit(3)"]) == 3

    def test_env_contract(self, tmp_path):
        """Workers receive the coordinator/rank env the init() consumes."""
        script = tmp_path / "w.py"
        script.write_text(
            "import os, sys\n"
            "assert os.environ['HVD_TPU_NUM_PROCESSES'] == '2'\n"
            "assert os.environ['HVD_TPU_PROCESS_ID'] in ('0', '1')\n"
            "assert ':' in os.environ['HVD_TPU_COORDINATOR_ADDR']\n"
        )
        assert run(2, [sys.executable, str(script)]) == 0

    def test_peer_failure_kills_job(self, tmp_path):
        script = tmp_path / "w.py"
        script.write_text(
            "import os, sys, time\n"
            "if os.environ['HVD_TPU_PROCESS_ID'] == '0':\n"
            "    sys.exit(7)\n"
            "time.sleep(60)\n"   # must be terminated, not waited for
        )
        assert run(2, [sys.executable, str(script)]) == 7

    def test_start_timeout_fires_when_no_worker_inits(self, tmp_path):
        """Workers that never reach hvd.init() (coordinator never binds)
        trip --start-timeout instead of hanging forever."""
        script = tmp_path / "sleeper.py"
        script.write_text("import time\ntime.sleep(300)\n")
        with pytest.raises(TimeoutError, match="failed to start"):
            run(2, [sys.executable, str(script)], start_timeout=3.0)

    def test_no_command_errors(self):
        from horovod_tpu.runner.launch import main

        assert main(["-np", "2"]) == 2

    def test_remote_hosts_route_to_agent_mesh(self, monkeypatch):
        """Non-local -H entries go through remote_run (round-4 verdict:
        the CLI used to error out here); end-to-end world formation is
        tests/multiproc/test_remote_launch_mp.py."""
        import horovod_tpu.runner.launch as launch

        seen = {}

        def fake_remote_run(hosts, command, **kw):
            seen["hosts"], seen["command"] = hosts, command
            return 0

        monkeypatch.setattr("horovod_tpu.runner.remote.remote_run",
                            fake_remote_run)
        assert launch.main(["-np", "2", "-H", "otherhost:8", "x"]) == 0
        assert seen["hosts"] == [("otherhost", 8)]
        assert seen["command"] == ["x"]

    def test_malformed_hosts_spec_rejected(self):
        from horovod_tpu.runner.launch import main

        assert main(["-H", ":3", "x"]) == 2

    def test_hostfile_parses_both_formats(self, tmp_path, monkeypatch):
        """Reference horovodrun hostfile ('host slots=N') and the
        compact 'host:N' form both route into the same -H path."""
        import horovod_tpu.runner.launch as launch

        hf = tmp_path / "hosts"
        hf.write_text("# cluster A\n"
                      "nodeA slots=4\n"
                      "nodeB:2\n"
                      "nodeC\n")
        seen = {}

        def fake_remote_run(hosts, command, **kw):
            seen["hosts"] = hosts
            return 0

        monkeypatch.setattr("horovod_tpu.runner.remote.remote_run",
                            fake_remote_run)
        assert launch.main(["--hostfile", str(hf), "x"]) == 0
        assert seen["hosts"] == [("nodeA", 4), ("nodeB", 2), ("nodeC", 1)]

    def test_hostfile_errors(self, tmp_path):
        from horovod_tpu.runner.launch import main

        assert main(["--hostfile", "/nonexistent", "x"]) == 2
        for bad in ("nodeA slots=xyz", "nodeA 4", "localhost:abc"):
            hf = tmp_path / "bad"
            hf.write_text(bad + "\n")
            assert main(["--hostfile", str(hf), "x"]) == 2, bad
        assert main(["-H", "a:1", "--hostfile", str(hf), "x"]) == 2

    def test_ssh_and_nics_flags_reach_remote_run(self, monkeypatch):
        """--ssh-port/--ssh-identity-file/--network-interfaces thread
        into remote_run as explicit parameters (reference horovodrun
        flags) — no environment side channels."""
        import horovod_tpu.runner.launch as launch
        from horovod_tpu.runner.remote import ssh_exec

        seen = {}

        def fake_remote_run(hosts, command, **kw):
            seen.update(kw)
            return 0

        monkeypatch.setattr("horovod_tpu.runner.remote.remote_run",
                            fake_remote_run)
        assert launch.main(["-H", "otherhost:1", "--ssh-port", "2222",
                            "--ssh-identity-file", "/id_rsa",
                            "--network-interfaces", "eth1,eth2",
                            "x"]) == 0
        assert seen["ssh_port"] == 2222
        assert seen["ssh_identity_file"] == "/id_rsa"
        assert seen["nics"] == ["eth1", "eth2"]

        # and ssh_exec turns the params into the ssh command line
        built = {}

        class FakeStdin:
            write = staticmethod(lambda _ : None)
            flush = staticmethod(lambda: None)
            close = staticmethod(lambda: None)

        class FakeProc:
            stdin = FakeStdin()

        import horovod_tpu.runner.remote as remote

        monkeypatch.setattr(
            remote.subprocess, "Popen",
            lambda cmd, **kw: built.update(cmd=cmd) or FakeProc())
        ssh_exec("otherhost", ["agent"], "aa", ssh_port=2222,
                 ssh_identity_file="/id_rsa")
        cmd = built["cmd"]
        assert "-p" in cmd and "2222" in cmd
        assert "-i" in cmd and "/id_rsa" in cmd

    def test_network_interfaces_filters_advertised_addresses(
            self, monkeypatch):
        """Services constructed with nics= advertise only those NICs
        (plus loopback); unknown names fail loudly."""
        import pytest

        from horovod_tpu.runner.common import network

        monkeypatch.setattr(
            network, "local_addresses",
            lambda: {"eth0": ["10.0.0.5"], "eth1": ["192.168.1.9"],
                     "lo": ["127.0.0.1"]})
        svc = network.BasicService("t", b"k" * 32, nics=["eth1"])
        try:
            ips = [ip for ip, _ in svc.addresses()]
            assert "192.168.1.9" in ips and "127.0.0.1" in ips
            assert "10.0.0.5" not in ips
        finally:
            svc.shutdown()
        bad = network.BasicService("t2", b"k" * 32, nics=["eth9"])
        try:
            with pytest.raises(ValueError, match="eth9"):
                bad.addresses()
        finally:
            bad.shutdown()
        svc3 = network.BasicService("t3", b"k" * 32)
        try:
            assert "10.0.0.5" in [ip for ip, _ in svc3.addresses()]
        finally:
            svc3.shutdown()

    def test_log_level_flag_reaches_workers(self, tmp_path, monkeypatch):
        from horovod_tpu.runner.launch import main

        monkeypatch.delenv("HOROVOD_LOG_LEVEL", raising=False)
        script = tmp_path / "w.py"
        script.write_text(
            "import os, sys\n"
            "sys.exit(0 if os.environ.get('HOROVOD_LOG_LEVEL') == 'debug'"
            " else 5)\n")
        # case-insensitive like the env var itself
        assert main(["-np", "1", "--log-level", "DEBUG", "--",
                     sys.executable, str(script)]) == 0
        # the launcher's own process env is never mutated
        assert "HOROVOD_LOG_LEVEL" not in __import__("os").environ

    def test_timeline_and_autotune_flags_reach_workers(self, tmp_path,
                                                       monkeypatch):
        """Reference horovodrun flags --timeline-filename /
        --timeline-mark-cycles / --autotune / --autotune-log-file map to
        their env vars, identically on every rank — per-rank path
        de-confliction is the library's job at ``hvd.init()`` (covering
        remote/LSF launches too; proven in
        tests/multiproc/test_observability_mp.py)."""
        from horovod_tpu.runner.launch import main

        for var in ("HOROVOD_TIMELINE", "HOROVOD_TIMELINE_MARK_CYCLES",
                    "HOROVOD_AUTOTUNE", "HOROVOD_AUTOTUNE_LOG"):
            monkeypatch.delenv(var, raising=False)
        tl = tmp_path / "t.json"
        script = tmp_path / "w.py"
        script.write_text(
            "import os, sys\n"
            "ok = (os.environ.get('HOROVOD_TIMELINE') == %r\n"
            "      and os.environ.get('HOROVOD_TIMELINE_MARK_CYCLES') == '1'\n"
            "      and os.environ.get('HOROVOD_AUTOTUNE') == '1'\n"
            "      and os.environ.get('HOROVOD_AUTOTUNE_LOG') == 'a.jsonl')\n"
            "sys.exit(0 if ok else 5)\n" % str(tl))
        assert main(["-np", "2", "--timeline-filename", str(tl),
                     "--timeline-mark-cycles", "--autotune",
                     "--autotune-log-file", "a.jsonl", "--",
                     sys.executable, str(script)]) == 0
        # the launcher's own process env is never mutated
        assert "HOROVOD_TIMELINE" not in __import__("os").environ

    def test_knob_flags_reach_workers(self, tmp_path, monkeypatch):
        """Reference horovodrun tunable-parameter flags map to their
        env vars (fusion threshold converted MB -> bytes)."""
        from horovod_tpu.runner.launch import main

        for var in ("HOROVOD_FUSION_THRESHOLD", "HOROVOD_CACHE_CAPACITY",
                    "HOROVOD_HIERARCHICAL_ALLREDUCE",
                    "HOROVOD_STALL_CHECK_DISABLE",
                    "HOROVOD_STALL_CHECK_TIME_SECONDS"):
            monkeypatch.delenv(var, raising=False)
        script = tmp_path / "w.py"
        script.write_text(
            "import os, sys\n"
            "e = os.environ\n"
            "ok = (e.get('HOROVOD_FUSION_THRESHOLD') == str(32 << 20)\n"
            "      and e.get('HOROVOD_CACHE_CAPACITY') == '128'\n"
            "      and e.get('HOROVOD_HIERARCHICAL_ALLREDUCE') == '1'\n"
            "      and e.get('HOROVOD_STALL_CHECK_DISABLE') == '1'\n"
            "      and e.get('HOROVOD_STALL_CHECK_TIME_SECONDS') == '30.0')\n"
            "sys.exit(0 if ok else 5)\n")
        assert main(["-np", "1", "--fusion-threshold-mb", "32",
                     "--cache-capacity", "128", "--hierarchical-allreduce",
                     "--no-stall-check",
                     "--stall-check-warning-time-seconds", "30",
                     "--", sys.executable, str(script)]) == 0

    def test_config_file_fills_params_cli_wins(self, tmp_path, monkeypatch):
        """--config-file (reference horovodrun analogue): flat YAML of
        long option names; explicit CLI flags beat file values; unknown
        keys and bad values are rejected loudly."""
        from horovod_tpu.runner.launch import main, parse_args

        monkeypatch.delenv("HOROVOD_FUSION_THRESHOLD", raising=False)
        cfg = tmp_path / "h.yaml"
        cfg.write_text("fusion-threshold-mb: 16\n"
                       "hierarchical-allreduce: true\n"
                       "log_level: debug\n")
        args = parse_args(["--config-file", str(cfg),
                           "--fusion-threshold-mb", "64", "--", "true"])
        assert args.fusion_threshold_mb == 64  # CLI wins
        assert args.hierarchical_allreduce is True
        assert args.log_level == "debug"

        bad = tmp_path / "bad.yaml"
        bad.write_text("no-such-flag: 1\n")
        with pytest.raises(SystemExit, match="unknown parameter"):
            parse_args(["--config-file", str(bad), "--", "true"])

        badval = tmp_path / "badval.yaml"
        badval.write_text("fusion-threshold-mb: not-a-number\n")
        with pytest.raises(SystemExit, match="bad value"):
            parse_args(["--config-file", str(badval), "--", "true"])

        # A CLI flag explicitly set to its DEFAULT value still wins
        # (presence in argv decides, not value-vs-default).
        resetcfg = tmp_path / "r.yaml"
        resetcfg.write_text("reset-limit: 5\n")
        args = parse_args(["--reset-limit", "0",
                           "--config-file", str(resetcfg), "--", "true"])
        assert args.reset_limit == 0
        # ...and the worker command's own flags never count as launcher
        # flags (REMAINDER excluded from the scan).
        args = parse_args(["--config-file", str(resetcfg), "--",
                           "prog", "--reset-limit", "9"])
        assert args.reset_limit == 5

        # choices are validated like the CLI validates them
        typo = tmp_path / "typo.yaml"
        typo.write_text("log-level: deubg\n")
        with pytest.raises(SystemExit, match="must be one of"):
            parse_args(["--config-file", str(typo), "--", "true"])

        # quoted booleans parse strictly; garbage is loud
        quoted = tmp_path / "q.yaml"
        quoted.write_text("hierarchical-allreduce: 'false'\n")
        assert parse_args(["--config-file", str(quoted), "--", "true"]
                          ).hierarchical_allreduce is False
        garbage = tmp_path / "g.yaml"
        garbage.write_text("hierarchical-allreduce: maybe\n")
        with pytest.raises(SystemExit, match="bad value.*boolean"):
            parse_args(["--config-file", str(garbage), "--", "true"])

        # 'help' is not an injectable parameter
        helpcfg = tmp_path / "h2.yaml"
        helpcfg.write_text("help: true\n")
        with pytest.raises(SystemExit, match="unknown parameter"):
            parse_args(["--config-file", str(helpcfg), "--", "true"])

        script = tmp_path / "w.py"
        script.write_text(
            "import os, sys\n"
            "sys.exit(0 if os.environ.get('HOROVOD_FUSION_THRESHOLD')"
            " == str(16 << 20) else 5)\n")
        assert main(["--config-file", str(cfg), "--",
                     sys.executable, str(script)]) == 0

    def test_abbreviated_flags_rejected(self, capsys):
        """allow_abbrev=False: a prefix like --fusion must error, not
        silently match --fusion-threshold-mb — the config-file
        explicit-CLI-wins scan compares argv against FULL option
        strings, so an abbreviation would let a file value shadow what
        the user typed."""
        from horovod_tpu.runner.launch import parse_args

        with pytest.raises(SystemExit):
            parse_args(["--fusion", "32", "--", "true"])
        capsys.readouterr()  # swallow argparse usage noise

    def test_config_file_without_pyyaml_names_the_extra(self, tmp_path,
                                                        monkeypatch):
        """With pyyaml absent, --config-file must fail with an
        actionable install hint, not a bare ImportError."""
        import sys as _sys

        from horovod_tpu.runner.launch import parse_args

        cfg = tmp_path / "h.yaml"
        cfg.write_text("verbose: true\n")
        monkeypatch.setitem(_sys.modules, "yaml", None)  # import → ImportError
        with pytest.raises(SystemExit, match="pyyaml"):
            parse_args(["--config-file", str(cfg), "--", "true"])

    def test_output_filename_writes_per_rank_files(self, tmp_path):
        """Reference horovodrun --output-filename: each rank's output
        lands in its own file pair instead of the launcher's tty."""
        from horovod_tpu.runner.launch import main

        outdir = tmp_path / "logs"
        script = tmp_path / "w.py"
        script.write_text(
            "import os, sys\n"
            "print('out-rank', os.environ['HVD_TPU_PROCESS_ID'])\n"
            "print('err-rank', os.environ['HVD_TPU_PROCESS_ID'],"
            " file=sys.stderr)\n")
        rc = main(["-np", "2", "--output-filename", str(outdir), "--",
                   sys.executable, str(script)])
        assert rc == 0
        for rank in (0, 1):
            assert (outdir / f"rank.{rank}.stdout").read_text() \
                == f"out-rank {rank}\n"
            assert (outdir / f"rank.{rank}.stderr").read_text() \
                == f"err-rank {rank}\n"

    def test_local_hosts_slots_set_world_size(self, tmp_path, monkeypatch):
        """`-H localhost:N` / a local hostfile sizes the world from the
        declared slots (reference horovodrun semantics) — previously the
        slot counts were silently ignored on the local path."""
        import horovod_tpu.runner.launch as launch

        seen = {}

        def fake_run(np_, command, **kw):
            seen["np"] = np_
            return 0

        monkeypatch.setattr(launch, "run", fake_run)
        hf = tmp_path / "hosts"
        hf.write_text("localhost slots=8\n")
        assert launch.main(["--hostfile", str(hf), "x"]) == 0
        assert seen["np"] == 8
        assert launch.main(["-H", "localhost:4", "x"]) == 0
        assert seen["np"] == 4
        assert launch.main(["-np", "2", "-H", "localhost:4", "x"]) == 0
        assert seen["np"] == 2   # explicit -np within slots is honored
        assert launch.main(["-np", "9", "-H", "localhost:4", "x"]) == 2


@pytest.mark.slow
class TestMultiProcessIntegration:
    def test_two_process_allreduce(self, tmp_path):
        """The reference CI pattern: the same pytest-style body under
        ``horovodrun -np 2`` — here two real processes rendezvous over
        jax.distributed (CPU backend) and allreduce."""
        script = tmp_path / "worker.py"
        script.write_text(
            "import os\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "import numpy as np\n"
            "import horovod_tpu as hvd\n"
            "hvd.init()\n"
            "assert hvd.cross_size() == 2, hvd.cross_size()\n"
            "x = np.full((3, 4), hvd.cross_rank() + 1.0, np.float32)\n"
            "out = np.asarray(hvd.allreduce(x, op=hvd.Sum))\n"
            "# reference semantics: elementwise sum of each process's tensor\n"
            "assert out.shape == x.shape, out.shape\n"
            "assert np.allclose(out, 1.0 + 2.0), out\n"
            "print('rank', hvd.cross_rank(), 'ok')\n"
        )
        import os

        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {"PYTHONPATH": repo_root + os.pathsep
               + os.environ.get("PYTHONPATH", "")}
        rc = run(2, [sys.executable, str(script)], start_timeout=180, env=env)
        assert rc == 0

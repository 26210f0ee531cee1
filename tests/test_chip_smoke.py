"""``chip_smoke.py``'s contract, as far as a machine without the chip
can hold it to: it never reports ok off the TPU, ``--chips 4`` runs the
multi-chip phases and nothing else, the last line has exactly the keys
the driver reads — and (slow) every phase runs end to end at a tiny
size on virtual CPU devices, the rehearsal to make before a chip call.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_refuses_to_report_ok_on_cpu(capsys):
    """The session's platform is the CPU: ``main`` must raise before
    any phase and print no result line."""
    for argv in ([], ["--chips", "4"]):
        with pytest.raises(RuntimeError, match="needs a TPU"):
            chip_smoke.main(argv)
    out = capsys.readouterr().out
    assert '"ok"' not in out and out.strip() == ""


def test_chips_4_selects_only_the_multichip_phases():
    one, four = chip_smoke.phases_for(1), chip_smoke.phases_for(4)
    assert one == ["collectives", "trainer", "server"]
    assert four == ["collectives", "data_parallel_trainer",
                    "dp_vs_single_device"]
    assert not {"trainer", "server"} & set(four)
    with pytest.raises(SystemExit):      # argparse: only 1 or 4
        chip_smoke.main(["--chips", "2"])


def test_final_line_schema():
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    line = chip_smoke.final_line([Dev()] * 4)
    assert line == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 4}}')
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}


# The same phases main() runs, at a size the CPU finishes in seconds.
# The size is chosen here: the script has no option that would let it
# pass off the chip.
_REHEARSAL = textwrap.dedent("""
    import sys
    sys.path.insert(0, {root!r})
    import chip_smoke
    import horovod_tpu as hvd

    hvd.init()
    tiny = chip_smoke.Sizes(
        vocab_size=512, n_layer=2, n_head=2, d_model=64, d_ff=128,
        seq_len=256, dtype="float32", batch_per_chip=2, train_steps=3,
        compare_batch=4, prompt_lens=(37, 9, 60, 100, 180),
        max_new_tokens=8)
    chip_smoke.run({chips}, tiny, 0)
""")


@pytest.mark.slow
@pytest.mark.parametrize("chips", [1, 4])
def test_whole_script_rehearsal_on_virtual_devices(chips):
    out = subprocess.run(
        [sys.executable, "-c", _REHEARSAL.format(root=ROOT, chips=chips)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS":
                 f"--xla_force_host_platform_device_count={chips}"})
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]
    assert [r["phase"] for r in rows] == chip_smoke.phases_for(chips)
    assert all(r["ok"] for r in rows)
    if chips == 4:
        assert rows[1]["batch_devices"] == 4
        assert rows[1]["collectives_in_program"] > 0
        assert rows[2]["max_abs_diff"] <= rows[2]["tolerance"]
    else:
        assert rows[2]["failed"] == 0
        assert len(rows[2]["prefill_buckets"]) >= 2

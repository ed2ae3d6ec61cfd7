"""chip_smoke.py, rehearsed end to end on the CPU.

The script is the driver's proof that the served commit path starts on
the chip. Here it runs at its rehearsal size — children and all — so a
wrong path, argument or comparison shows before chip time is spent. A
rehearsal can never pass for a chip run, and a run without ``--rehearse``
must refuse a machine with no TPU.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(cmd):
    done = subprocess.run([sys.executable, *cmd], cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    lines = [json.loads(x) for x in done.stdout.splitlines()]
    return done.returncode, lines, done.stderr


def test_rehearsal_runs_both_phases_and_holds_the_reference():
    rc, lines, err = _run([SMOKE, "--rehearse"])
    assert rc == 0, err[-2000:]
    facts = {x["phase"]: x for x in lines[:-1]}
    # both children ran to their end: the inproc child reports last,
    # and the served line is only written after fdbserver's own status
    # answered and before it is told to stop
    assert set(facts) == {"inproc.sync", "inproc.thread", "inproc", "served"}
    assert facts["inproc"]["ok"] is True
    for name in ("inproc.sync", "inproc.thread", "served"):
        f = facts[name]
        assert f["missed_conflicts"] == 0
        assert f["conflicts"] == f["ref_conflicts"] + f["extra_conflicts"]
        assert f["ref_conflicts"] > 0  # the scripted races were refused
        assert f["extra_conflicts"] <= 0.01 * f["txns"]
        assert 0 < f["final_rows"] < f["rows"]
        assert f["kernel_routes"].get("jit", 0) > 0
        assert not f["fallbacks"].get("pallas_to_jit")
        assert f["compile"]["backend_compiles"] > 0
    assert any(int(b) > 1 for b in facts["inproc.thread"]["bucket_histogram"])
    # a rehearsal never passes for a chip run
    assert lines[-1] == {"ok": False, "rehearsal": True,
                         "device": facts["inproc"]["device"]}


def test_without_rehearse_a_machine_with_no_tpu_is_refused():
    rc, lines, err = _run([SMOKE])
    assert rc != 0
    assert lines[-1]["ok"] is False
    assert "no TPU" in lines[-1]["error"] or "no TPU" in err
    assert not any("rows" in x for x in lines)  # refused before loading


def test_a_corrupted_reference_fails_the_run():
    """Steered from here, not by an option of the script: the inproc
    phase with a reference that lost one refused transaction."""
    prog = (
        "import sys, chip_smoke as cs\n"
        "plain = cs.reference\n"
        "def corrupted(sizes, seed):\n"
        "    conflicted, rows = plain(sizes, seed)\n"
        "    return conflicted | {0}, rows\n"
        "cs.reference = corrupted\n"
        "sys.exit(cs.main(['--phase', 'inproc', '--rehearse']))\n"
    )
    rc, lines, err = _run(["-c", prog])
    assert rc != 0
    assert "missed conflicts" in err
    assert not any(x.get("ok") for x in lines)

"""CPU rehearsal of chip_smoke.py: its phase functions driven at reduced
size, so a change that breaks the script's control flow fails here and not
on the chip. The script itself refuses to run without a TPU."""
import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu(capsys):
    assert _chip_smoke().main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out


def test_chip_smoke_phases_rehearse_on_cpu(capsys):
    """Streamed phase against the all-resident phase, as on the chip.
    Reduced switch-base-128 keeps one MoE layer whose 4 experts are the
    slot floor, so nothing could stream; reduced qwen3-moe has two MoE
    layers, and 0.25 residency leaves half of its 8 experts on the host."""
    cs = _chip_smoke()
    size = dict(arch="qwen3-moe-235b-a22b", reduced=True, requests=2,
                max_new=4)
    a = cs.run_phase("a", cs.serve_argv(0.25, **size), streamed=True)
    b = cs.run_phase("b", cs.serve_argv(1.0, **size))
    cs.compare(a, b)
    out = capsys.readouterr().out
    assert out.count("guard: zero-recompile ok") == 2
    assert "phase a: slot hits=" in out and "wall: drain=" in out
    assert "agreement a vs b: first-token 2/2 full-sequence 2/2" in out
    # the result line is main's alone
    assert not any(line.startswith("{") and json.loads(line).get("ok")
                   for line in out.splitlines())

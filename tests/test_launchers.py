"""Launcher entry points (serve.py / train.py) run end-to-end on reduced
configs — the deployment path a user actually invokes."""
import jax

from repro.launch import serve as serve_mod
from repro.launch import train as train_mod


def test_serve_launcher_reduced(capsys):
    serve_mod.main(["--arch", "qwen3-moe-235b-a22b", "--reduced",
                    "--requests", "2", "--prompt-len", "6", "--max-new", "3"])
    out = capsys.readouterr().out
    assert "hit=" in out and "sim-tok-lat=" in out


def test_train_launcher_reduced(capsys, tmp_path):
    ckpt = str(tmp_path / "t.npz")
    train_mod.main(["--arch", "qwen3-1.7b", "--reduced", "--steps", "3",
                    "--batch", "2", "--seq", "32", "--ckpt", ckpt])
    out = capsys.readouterr().out
    assert "step" in out and "loss" in out
    import os
    assert os.path.exists(ckpt)

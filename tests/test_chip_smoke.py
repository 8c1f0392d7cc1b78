"""chip_smoke.py's own logic, driven on the CPU at a tiny size: the two
ways a smoke goes falsely green must both end non-zero — a server that
answers every request from the wrong platform, and a request phase that
never reached a server."""

from __future__ import annotations

import ast
import os
import shutil
import socket
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

# the verify skill's shrunken CPU server
TINY_ARGS = [
    "--model-model-name", "tiny", "--model-dtype", "float32",
    "--engine-num-pages", "192", "--engine-page-size", "8",
    "--engine-max-pages-per-seq", "64", "--engine-prefill-buckets", "16,64",
]


def test_cpu_server_answers_then_fails_the_platform_assertion(capsys):
    """With JAX_PLATFORMS=cpu and ``tiny`` every request phase passes —
    and the smoke still fails, on the platform assertion, with no result
    line printed."""
    with pytest.raises(chip_smoke.SmokeFailure, match="not 'tpu'"):
        chip_smoke.main(server_args=TINY_ARGS, platform="cpu")
    out = capsys.readouterr().out
    for phase in ("PASS server ready", "PASS /generate x2", "PASS /chat",
                  "PASS /v1/completions", "PASS burst", "PASS /server/stats"):
        assert phase in out, out
    assert "PASS device facts" not in out
    assert '"ok"' not in out


def test_request_phase_against_a_dead_port_fails():
    with socket.socket() as s:  # a port nothing listens on
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    with pytest.raises(OSError):
        chip_smoke.request_phases(f"http://127.0.0.1:{port}")


def test_alone_in_a_directory_it_exits_nonzero_and_prints_no_result(tmp_path):
    """The script by itself, through its ``__main__`` guard, in a
    directory that holds nothing else of the repo: the server child
    cannot even be imported, so the smoke exits non-zero with no result
    line (and never gets as far as jax or a chip)."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert run.returncode != 0
    assert '"ok"' not in run.stdout
    assert "FAIL server exited" in run.stderr


def test_kernel_probe_logic_in_interpret_mode():
    """tools/kernel_probe.py's own logic (inputs, references, the
    agreement check) at a tiny geometry in interpret mode: every kernel
    record must come back compiled, finite and agreeing. On the chip the
    same code runs with interpret=False at serving geometry."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import kernel_probe

    eng = dict(max_batch=4, page_size=8, num_pages=64, max_pages_per_seq=8,
               prefill_batch=2, prefill_buckets=[16])
    geo = dict(H=4, KV=4, D=64, hidden=256)  # two 128-lane head chunks
    recs = list(kernel_probe.probe_geometry("tiny", geo, eng,
                                            interpret=True, mixed_width=16))
    assert [r["kernel"] for r in recs] == [
        "decode", "decode_int8_pool", "prefill_T16", "ragged", "rms_norm",
        "rope", "q8_matmul", "q4_matmul"]
    for r in recs:
        assert r["compiled"] and r["agrees"] and r["finite"], r


def test_parent_never_imports_jax():
    """The chip belongs to the server child; the smoke's own process
    must stay off jax."""
    tree = ast.parse(open(os.path.join(ROOT, "chip_smoke.py")).read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert "jax" not in imported
    assert "distributed_inference_server_tpu" not in imported

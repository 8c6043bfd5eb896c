"""chip_smoke.py on the CPU: it must refuse to pass here, and its phase
functions must run end to end at toy width.

The toy sizes are a test-only argument of the phase functions — the
program itself has no small mode. Pallas kernels run in the interpreter
(PT_PALLAS=interpret), steered from here.
"""

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402

# dropout off: six steps on one batch then fall monotonically
TOY_BERT = dict(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                num_attention_heads=2, intermediate_size=64,
                max_position_embeddings=64, hidden_dropout_prob=0.0,
                attention_probs_dropout_prob=0.0)
TOY_TRAIN = dict(layers=2, batch=4, seq=16, max_preds=4, steps=6)
TOY_SERVE = dict(vocab=128, d_model=32, n_head=2, n_layers=1, d_inner=64,
                 max_seq_len=64, slots=4, page=8, prefill_bucket=32,
                 prompt_lens=(20, 24, 17, 30), max_new=4)


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("PT_PALLAS", "interpret")


def _run(args, env_extra, cwd=REPO_ROOT):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable] + args, env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=300)


def test_cpu_run_fails_and_prints_no_result():
    """No CPU fallback: on the CPU the program exits non-zero and prints
    no `"ok": true` line."""
    r = _run([os.path.join(REPO_ROOT, "chip_smoke.py")],
             {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "need platform 'tpu'" in r.stderr


def test_four_chip_option_fails_on_cpu_too():
    r = _run([os.path.join(REPO_ROOT, "chip_smoke.py"), "--chips", "4"],
             {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_device_phase_reports_what_jax_found():
    info = chip_smoke.phase_device("cpu", 4)     # conftest: 8 virtual
    assert info == {"platform": "cpu", "kind": "cpu", "count": 8}
    with pytest.raises(chip_smoke.SmokeFailure, match="need 16 device"):
        chip_smoke.phase_device("cpu", 16)


def test_train_phase_toy_width(interpret, capsys):
    losses = chip_smoke.phase_train(size=TOY_TRAIN, hidden=TOY_BERT,
                                    cache=chip_smoke.CacheWatch())
    assert len(losses) == TOY_TRAIN["steps"] and losses[-1] < losses[0]
    out = capsys.readouterr().out
    assert '"flash_route": "pallas_interpret"' in out
    assert '"block_until_ready_closed_step_ms"' in out


def test_train_phase_fails_off_the_kernels(monkeypatch):
    """A forced failure exits the phase: with the kernels off the flash
    route is the reference and the phase refuses it."""
    monkeypatch.setenv("PT_PALLAS", "off")
    with pytest.raises(chip_smoke.SmokeFailure, match="route 'reference'"):
        chip_smoke.phase_train(size=TOY_TRAIN, hidden=TOY_BERT)


def test_serve_phase_toy_width(interpret, tmp_path, capsys):
    from paddle_tpu.core import flags

    # two 32-token KV chunks over the 64-token context: the multi-chunk
    # branch, as at the real width
    with flags.overrides(pallas_kv_chunk_tokens=32):
        chip_smoke.phase_serve(size=TOY_SERVE, work_dir=str(tmp_path))
    out = capsys.readouterr().out
    assert out.count('"phase": "serve"') == 2        # fp32, then int8
    assert '"int8_gemm_dispatches"' in out and '"kv_chunks": 2' in out
    assert not os.listdir(tmp_path)                  # model dir removed


def test_serve_phase_fails_on_a_single_chunk(interpret, tmp_path):
    """At the default chunk the toy context is one chunk: the phase
    refuses to pass on the branch it was not meant to compile."""
    with pytest.raises(chip_smoke.SmokeFailure, match="fits one KV chunk"):
        chip_smoke.phase_serve(size=TOY_SERVE, work_dir=str(tmp_path))


def test_sharded_phase_on_four_virtual_devices(interpret, capsys):
    sharded, one = chip_smoke.phase_sharded(
        size=dict(TOY_TRAIN, steps=3), n_devices=4, hidden=TOY_BERT)
    assert len(sharded) == len(one) == 3
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(rec["state_share_per_device"]) == {"0", "1", "2", "3"}
    assert min(rec["state_share_per_device"].values()) >= 0.25
    assert rec["arrays_split_across_devices"] > 0


_CACHE_PROBE = (
    "import paddle_tpu, jax;"
    "from paddle_tpu.core import compile_cache as cc;"
    "print(cc.enable_compile_cache());"
    "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_honours_the_environment(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX is left to it, no code sets
    another directory."""
    r = _run(["-c", _CACHE_PROBE],
             {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(tmp_path), str(tmp_path)]


def test_compile_cache_fixed_path_across_processes(tmp_path):
    """Unset: the same in-checkout path from two processes started in
    different directories."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    outs = []
    for cwd in (REPO_ROOT, str(tmp_path)):
        r = subprocess.run(
            [sys.executable, "-c", _CACHE_PROBE], cwd=cwd, timeout=300,
            env=dict(env, PYTHONPATH=REPO_ROOT), capture_output=True,
            text=True)
        assert r.returncode == 0, r.stderr
        outs.append(r.stdout.split())
    want = os.path.join(REPO_ROOT, ".jax_cache")
    assert outs == [[want, want], [want, want]]

"""chip_smoke.py's refusal on a host with no TPU.  (Its phases need
the TPU, run as `python chip_smoke.py` on one chip; phase E's code is
rehearsed here at a small size, its kernel interpreted.)"""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_the_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'chip_smoke.py')], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS='cpu'), capture_output=True,
        text=True, timeout=60)
    assert proc.returncode != 0
    assert 'no TPU' in proc.stderr and "JAX_PLATFORMS='cpu'" in proc.stderr
    # refused before anything was built, compiled or reported
    assert 'native:' not in proc.stdout and '"ok"' not in proc.stdout


def _chip_smoke():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    return chip_smoke


def test_result_line_has_the_contract_keys_and_no_others():
    import jax
    chip_smoke = _chip_smoke()
    devices = jax.devices()
    line = chip_smoke.result_line(devices)
    assert '\n' not in line
    got = json.loads(line)
    assert got == {'ok': True, 'device': {
        'platform': devices[0].platform, 'kind': devices[0].device_kind,
        'count': len(devices)}}
    assert isinstance(got['device']['kind'], str)
    assert isinstance(got['device']['count'], int)


def test_phase_e_compares_the_three_forms():
    """Phase E at 2 sequences of 64 rows of 256 channels: the three
    forms of the causal convolution agree and each is timed."""
    result = _chip_smoke().phase_e(rows=128, channels=256, seq_len=64,
                                   calls=1, expect_custom_call=False)
    assert set(result) == {'xla', 'cast_after_shift', 'kernel', 'worst'}
    assert result['worst'] < 1e-2

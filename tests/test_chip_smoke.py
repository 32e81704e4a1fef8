"""chip_smoke.py's refusal: what the driver checks first, in a sandbox
with no chip.  (Its phases need the TPU and run through the chip tool.)"""
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


def test_result_line_has_the_contract_keys_and_no_others():
    import jax
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    devices = jax.devices()
    line = chip_smoke.result_line(devices)
    assert '\n' not in line
    got = json.loads(line)
    assert got == {'ok': True, 'device': {
        'platform': devices[0].platform, 'kind': devices[0].device_kind,
        'count': len(devices)}}
    assert isinstance(got['device']['kind'], str)
    assert isinstance(got['device']['count'], int)

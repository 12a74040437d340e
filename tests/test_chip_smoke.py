"""chip_smoke.py off the chip: it must fail, say what it found, and print
no result line (the on-chip run is the builder's and the driver's)."""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_without_a_chip_exits_nonzero_naming_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(_REPO, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr
    # the device is named first, as JAX reports it; there is no "ok" line
    lines = [json.loads(ln) for ln in p.stdout.splitlines() if ln.strip()]
    assert lines[0]["phase"] == "device" and lines[0]["platform"] == "cpu"
    assert set(lines[0]["versions"]) == {"jax", "jaxlib", "libtpu"}
    assert not any("ok" in ln for ln in lines)

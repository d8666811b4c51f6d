"""``chipbench/hoist.py`` patches a private JAX function so that the
engine's closed-over weights compile as arguments. These tests fail when
that function changes under a new JAX, so that the patch is looked at
again, and show on the CPU that the patch does what it says."""

import hashlib
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
from jax._src import core, literals

ROOT = Path(__file__).resolve().parents[2]

# sha256 of ``def jaxpr_const_args`` in jax/_src/core.py (JAX 0.9.0), the
# function ``hoist.enable`` wraps.
CONST_ARGS_SHA256 = ("67fdb02a66234ec61f7e4d305c490b7da90ba805d9e11efe51d7a6"
                     "866d0426c4")

PROBE = """
import sys
sys.path.insert(0, sys.argv[1])
from chipbench import hoist
hoist.enable()
import jax, jax.numpy as jnp, numpy as np
w_host = np.random.default_rng(0).normal(size=(256, 256)).astype(np.float32)
w = jax.device_put(w_host)
f = jax.jit(lambda x: (x @ w) * jnp.arange(256.0))
x = np.ones((4, 256), np.float32)
main = f.lower(x).as_text().split("func.func public @main")[1]
print(main.split(")")[0])
want = (x @ w_host) * np.arange(256.0)
print("close", bool(np.allclose(np.asarray(f(x)), want, rtol=1e-4)))
"""


def test_the_patched_jax_function_is_unchanged():
    src = Path(core.__file__).read_text()
    body = re.search(r"^def jaxpr_const_args\(.*?(?=^\S)", src,
                     re.S | re.M)
    assert body is not None, "jax._src.core.jaxpr_const_args is gone"
    digest = hashlib.sha256(body.group(0).encode()).hexdigest()
    assert digest == CONST_ARGS_SHA256, (
        "jax._src.core.jaxpr_const_args changed: check chipbench/hoist.py "
        "against it, then record the new digest here")
    assert callable(core.jaxpr_const_args)
    assert "val" in dir(literals.TypedNdArray)
    assert hasattr(jax.config, "jax_use_simplified_jaxpr_constants")


def test_enable_passes_closed_over_arrays_as_arguments():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", PROBE, str(ROOT)], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    signature, close = p.stdout.strip().splitlines()[-2:]
    assert "tensor<256x256xf32> {jax.const = true}" in signature
    assert close == "close True"

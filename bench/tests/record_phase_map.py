"""Record the compiled text of the program that ``record_trace.py`` traces
(run on a TPU, after it):

    python3 bench/tests/record_phase_map.py

``sinkhorn_log`` on the same tiny problem (80 x 80, 100 iterations),
compiled afresh with the persistent compilation cache off, so that its
instructions carry the solver's scopes in their ``op_name`` metadata and
the names that ``data/sinkhorn_scoped.xplane.pb`` holds.  Source paths in
the text are written relative to the checkout.
"""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(HERE.parent)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main() -> None:
    from repro.core import sinkhorn_log

    jax.config.update("jax_enable_compilation_cache", False)
    f32 = jnp.float32
    vec = jax.ShapeDtypeStruct((80,), f32)
    text = sinkhorn_log.lower(jax.ShapeDtypeStruct((80, 80), f32), vec, vec,
                              eps=0.01, max_iters=100, tol=1e-8).compile().as_text()
    out = HERE / "data" / "sinkhorn_scoped.hlo.txt"
    out.write_text(text.replace(str(ROOT) + "/", ""))
    print(f"{out.name}: {len(text)} characters, {jax.devices()[0].device_kind}")


if __name__ == "__main__":
    main()

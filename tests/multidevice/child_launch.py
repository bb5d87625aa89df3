"""Launch-stack check on a small real mesh (8 host devices): build_cell ->
jit(in/out shardings) -> lower -> compile for a full-config cell, and the
trip-count analyzer sees the layer loop.  Subprocess-only (XLA_FLAGS)."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

from repro.analysis.hloflow import analyze_hlo
from repro.launch.specs import build_cell

mesh = jax.make_mesh((4, 2), ("data", "model"))

for arch, shape, variant in [
    ("xlstm-125m", "decode_32k", "baseline"),
    ("xlstm-125m", "long_500k", "baseline"),
    ("recurrentgemma-2b", "decode_32k", "kv_int8"),
]:
    with jax.set_mesh(mesh):
        step, args, in_specs, out_specs, donate, meta = build_cell(
            arch, shape, mesh, variant=variant)
        compiled = jax.jit(step, in_shardings=in_specs,
                           out_shardings=out_specs,
                           donate_argnums=donate).lower(*args).compile()
    ma = compiled.memory_analysis()
    flow = analyze_hlo(compiled.as_text())
    assert ma.temp_size_in_bytes >= 0
    assert flow.dot_flops > 0, (arch, shape)
    # the scanned layer stack must appear as a multiplied loop
    assert any(t > 1 for _, t, _ in flow.loops), (arch, shape, flow.loops)
    print(f"launch OK {arch}/{shape}/{variant}: "
          f"dotflops={flow.dot_flops:.3g} loops={flow.loops[:2]}")

print("ALL LAUNCH-STACK CHECKS PASSED")

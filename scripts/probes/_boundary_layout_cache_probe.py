"""Does an executable with a non-default BOUNDARY layout survive JAX's
persistent compilation cache on this backend?

    chiprun -- sh -c 'python3 scripts/probes/_boundary_layout_cache_probe.py; \
                      python3 scripts/probes/_boundary_layout_cache_probe.py'

Run it TWICE in one chip call (two processes, one cache directory): the first
process compiles, the second is served from the cache. Each relays an f32
``[4096, 2, 512]`` array into ``major_to_minor=(1, 0, 2), tiling=((8, 128),)``
— the layout the TPU compiler wants a decoder master in — through a jitted
identity whose ``out_shardings`` is that ``Format``, and prints: the layout the
executable says it returns, the layout the returned array is labelled with,
and the worst error of a consumer compiled for the label and of one compiled
for the format. With jax / jaxlib 0.9.0 on a TPU v5e (PR 36, PERF.md §6) the
first process prints the layout asked for and errors of 1.5e-5, the second
process prints the DEFAULT layout as the label and errors of ≈ 114: the data
is not in the layout the array claims. Until both processes print the same,
a non-default layout stays inside a program (docs/TUNING.md "The optimizer
update's layout"; ROADMAP S1).
"""
from __future__ import annotations

import _bootstrap  # noqa: F401  (repo-root sys.path + cwd shim)

import sys


def main() -> int:
    import jax
    import numpy as np
    from jax.experimental.layout import Format, Layout

    from crosscoder_tpu.utils import compile_cache

    print("cache:", compile_cache.enable(), "| device:", jax.devices()[0].device_kind)
    host = np.random.default_rng(0).standard_normal((4096, 2, 512)).astype(np.float32)
    x = jax.device_put(host, jax.devices()[0])
    fmt = Format(Layout(major_to_minor=(1, 0, 2), tiling=((8, 128),)), x.sharding)
    relay = jax.jit(lambda a: a, out_shardings=fmt).lower(x).compile()
    print("the executable says it returns:", relay.output_formats.layout.major_to_minor)
    y = relay(x)
    print("the array is labelled:", y.format.layout.major_to_minor, y.format.layout.tiling)
    print("device to host equal:", np.array_equal(np.asarray(y), host))
    want = host[:, 1, :].sum(-1)

    def rows(a):
        return a[:, 1, :].sum(-1)

    got = np.asarray(jax.jit(rows)(y))
    print("consumer compiled for the label: max abs err", float(np.abs(got - want).max()))
    by_format = jax.jit(rows, in_shardings=fmt).lower(
        jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)).compile()
    try:
        got = np.asarray(by_format(y))
        print("consumer compiled for the format: max abs err",
              float(np.abs(got - want).max()))
    except ValueError as e:
        print("consumer compiled for the format refuses the array:", str(e)[:160])
    return 0


if __name__ == "__main__":
    sys.exit(main())

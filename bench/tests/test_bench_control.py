"""The control at a size a test run holds: the reference put in the
program's place and computed with float8 matrix products has to come out as
not correct under the cell's limits, where the program comes out correct."""

import jax

import bench_smoke
import check
import train_bench


def test_control_fails_where_the_program_passes():
    loaded = bench_smoke.get("l7x4")
    run = train_bench.Run(loaded, jax.devices()[:1])
    run.compile()
    prog = run.first_steps(2**32 + 9)
    run.free()
    prog["update"] = run.update()
    ref = run.reference()
    ctl = run.reference(matmul=check.fp8_matmul)
    limits = loaded["limits"]
    assert check.verdict(check.readings(prog, ref), limits)
    assert not check.verdict(check.readings(ctl, ref), limits)

"""The VPU calibration kernel computes what it counts (interpret mode; the
reading itself only means something on the chip)."""
import calibrate_vpu


def test_kernel_runs_every_step():
    # reading() raises unless every one of the 64 additions happened
    assert calibrate_vpu.reading(vregs=2, iters=64, calls=1,
                                 interpret=True) > 0

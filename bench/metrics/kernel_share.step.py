"""% of the first device's busy time in the window spent in the generated
Pallas kernels (device operations named with the codegen's kernel prefix);
the rest is the pads, slices and interior writes around them."""

from bench import spans


def read(rec):
    return spans.kernel_share(rec)

"""Kernel: the chain (pipeline) megakernel's share of its roofline.  The
roofline time of every chained request served in the traced window, over
the device time of the kernel's ops in the trace."""

#: How the trace names a Pallas kernel today: every megakernel is an op
#: ``%fn.N = ... custom-call(...), custom_call_target="tpu_custom_call"``,
#: so the reader reads only windows that served chained requests alone.
KERNEL = 'custom_call_target="tpu_custom_call"'


def read(ctx):
    return ctx.roofline_pct("pipeline", lambda name: KERNEL in name)

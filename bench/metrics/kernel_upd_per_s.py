"""Node-interval updates per second of the Mosaic sweep kernel.

The updates the halving schedule needs in the traced tunes (lanes alive
in each rung times the rung's intervals times the nodes, no padding;
the tune kind's ``schedule_updates``) over the device time of the
kernel's operations.  On the TPU a ``pallas_call`` compiles to an HLO
``custom-call`` whose target is ``tpu_custom_call``, named for the
jitted program around it (``%program.1``), not for the kernel; in this
cell the sweep kernel is the only one.  An operation is matched by its
own opcode (`` custom-call(``), not by an operand's name: a fusion that
reads the kernel's output names ``%pallas_call.<n>`` among its operands.
``AllocateBuffer`` custom calls reserve scratch and are not the kernel.
"""

KERNEL = ("tpu_custom_call", " custom-call(")
NOT_KERNEL = "AllocateBuffer"


def read(ctx):
    t = ctx["trace"]
    ns = 0
    for name, v in t.ops_ns.items():
        text = name + " " + t.ops_meta.get(name, "")
        if any(p in text for p in KERNEL) and NOT_KERNEL not in text:
            ns += v
    if ns <= 0:
        return None
    gen = ctx["gen"]
    return sum(gen.updates) / (ns * 1e-9)

"""Layer: device. The peak of device memory on the fullest chip, read once the
window has closed and before the reference runs: the allocator's peak of live
buffers since the process began plus the scratch that the step's executable
takes while it runs (``run.device_memory`` says why they are two numbers).
The comparison keeps nothing on the device before it is read (host copies
only, ``compare.drive_first_steps``), so both parts are the program's own and
a change that shrinks what the step holds moves it."""


def read(ctx):
    if ctx["rehearse"]:     # a CPU run says nothing about the chip's memory
        return None
    return ctx["memory_peak_bytes"] / 1e9 or None

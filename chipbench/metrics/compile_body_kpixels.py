"""Compile stage: the mean size, in thousands of pixels, of the program
body each compile in the window is unrolled over: ``body_pixels`` of the
``compile`` stage events that ended inside the window, which a kernel's
``KernelBenchSpec.program_shape`` supplies (harris: its halo-extended
band).  ``None`` where no event carries it: add, or a program without the
counter."""


def read(run):
    pixels = [
        float(ev["body_pixels"])
        for ev in run.events
        if ev.get("ev") == "stage" and ev.get("stage") == "compile" and "body_pixels" in ev
        and run.start <= float(ev["t"]) <= run.deadline
    ]
    if not pixels:
        return None
    return sum(pixels) / len(pixels) / 1e3

"""Share of the traces of ``sinkhorn_log`` at the cell's cost shape that
took the VMEM-resident kernel's route, as the system's route counter
(``repro.utils.trace.routes``) recorded them: 1.0 when every build of the
solver at that shape runs the kernel, 0.0 when all run the XLA loop.
Nothing where the program has no route counter."""


def read(run, reduced):
    try:
        from repro.utils.trace import routes
    except ImportError:
        return None
    cfg = run.config
    shape = (int(cfg["num_classes"]) * int(cfg["samples_per_class"]), int(cfg["num_target"]))
    mine = [r.route for r in routes() if r.fun_name == "sinkhorn_log" and tuple(r.shape) == shape]
    if not mine:
        return None
    return sum(r == "resident" for r in mine) / len(mine)

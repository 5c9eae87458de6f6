"""Model step (the whole solve): model FLOPs of the samples completed
between the window's first and last completion (2 x NFE forwards from
shapes, ``counts.sample_flops``) over that time times the chip's bf16
peak. Padded lanes and the solver's own work do not count. Backlog
cells, where it moves ``samples_per_s`` one for one."""


def read(run):
    if len(run.events) < 2 or not run.peaks.get("bf16_flops"):
        return None
    ta, tb = run.events[0][0], run.events[-1][0]
    flops = sum(run.sample_flops for r in run.records
                if r.status == "ok" and ta < r.done <= tb)
    chips = run.cell.chips
    return 100.0 * flops / ((tb - ta) * run.peaks["bf16_flops"] * chips)

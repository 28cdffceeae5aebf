"""Single-chip driver: device programs the driver launched per wave in
the window, from its own tally (WaveScheduler.stats["dispatches"] over
stats["waves"], both cumulative). The trace's
device_launches_per_kpod counts every program the chip ran, the
Packer's unpack programs too, which the tally leaves out."""


def snapshot(ctx):
    stats = ctx["sched"].scheduler.config.algorithm._wave.stats
    return {"dispatches": stats.get("dispatches"), "waves": stats["waves"]}


def read(run):
    before, after = run["snapshots"]["dispatches_per_wave"]
    waves = after["waves"] - before["waves"]
    if after["dispatches"] is None or not waves:
        return None
    return (after["dispatches"] - before["dispatches"]) / waves

"""Single-chip driver: the steps the wave loop ran per wave in the
window, from its own tally (WaveScheduler.stats["steps_by_kind"], the
`Step`s of models/waveloop.run_wave by kind: `scan`, `single`,
`group_host`, `group_device`, summed; over stats["waves"]; both
cumulative). Every step that is not the scan's is a device round trip
the loop waits for, and each is preceded by a flush of the scan where
pods are pending: on a cluster of one kind of pod a wave is one step or
one a run, on a mixed one the kinds alternate. A program that keeps no
such tally gives nothing to read."""


def snapshot(ctx):
    stats = ctx["sched"].scheduler.config.algorithm._wave.stats
    if "steps_by_kind" not in stats:
        return {}
    return {"steps": sum(stats["steps_by_kind"].values()),
            "waves": stats["waves"]}


def read(run):
    before, after = run["snapshots"]["steps_per_wave"]
    if not after:
        return None
    waves = after["waves"] - before["waves"]
    if not waves:
        return None
    return (after["steps"] - before["steps"]) / waves

"""Single-chip driver: bytes shipped host to device per 1,000 pods
bound in the window (Packer.total_h2d_bytes, cumulative)."""


def snapshot(ctx):
    from kubernetes_tpu.models.pack import Packer

    return {"bytes": int(Packer.total_h2d_bytes)}


def read(run):
    before, after = run["snapshots"]["h2d_bytes_per_kpod"]
    bound = run["loadgen"]["bound_in_window"]
    if not bound:
        return None
    return (after["bytes"] - before["bytes"]) / (bound / 1000.0)

"""Scheduling models: the batched tensor scheduler and algorithm providers.

`batch.BatchScheduler` is the flagship model — the reference's
generic_scheduler re-expressed as one jitted loop over the pending-pod
axis (a step a pod, as far as the backlog's real length) with per-step
O(nodes) masked kernels (SURVEY.md §7 stages 2-3).
`providers` is the plugin registry seam (factory/plugins.go semantics).
"""

from kubernetes_tpu.models.batch import BatchScheduler, SchedulerConfig

__all__ = ["BatchScheduler", "SchedulerConfig"]

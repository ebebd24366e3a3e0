"""Host milliseconds per protocol pass inside the scheduler's
`schedule()` calls, from the benchmark's own span around the policy.
Mean over the passes of the traced window."""


def read(obs):
    sched = obs.get("sched_s")
    if not sched:
        return None
    return 1e3 * sum(sched) / len(sched)

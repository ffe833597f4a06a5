"""The pick's regret at the configuration's ``regret_size``, in percent:
the measured time of the variant and block size that the selection picks,
over the fastest of all candidates measured on the same matrix, less
one."""


def read(run):
    r = run.counters.get("regret")
    return None if r is None else 100.0 * r["regret"]

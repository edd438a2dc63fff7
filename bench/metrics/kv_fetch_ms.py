"""Host time spent inside ``HostKVStore.fetch`` per hit batch, summed over
the batch's requests (host clock, from the benchmark's subclass of the
store).  Nothing to read in a window without hits."""


def read(run):
    hits = [b["fetch_s"] for b in run.batches if b["hit"]]
    return sum(hits) / len(hits) * 1e3 if hits else None

"""Running totals of what the served path moved, for this process.

Plain integers, added once per call (never per token) at the layer
boundaries of ``engine.py`` and ``host_store.py``:

* ``kv.fetch.to_device_bytes``: bytes of every upload inside
  ``HostKVStore.fetch``, which copies nothing back (the cache state: K/V,
  or multi-head latent attention's latent and rotated key);
* ``kv.fetch.tokens``: context tokens fetched;
* ``kv.pull.to_host_bytes``: cache state pulled to the host after prefill;
* ``cache.rebuild.batches``: batches whose decode cache the device laid
  out, a hit's from its fetched state and a miss's from its prefill state;
* ``decode.host_syncs``: device-to-host syncs in the decode loop;
* ``decode.cache.in_place_steps``: decode steps, ``step.first`` on a hit
  included, whose input cache the step took by donation (its buffers
  deleted after the call), so that the step updated the cache in place;
  0 where the backend refused the donation;
* ``moe.routes`` and ``moe.routes.local``: a MoE model's routes in decode
  steps (every token's top-k, over every MoE layer) and those to the
  experts this device holds, summed on the device and read once a batch;
* ``moe.held_dense.steps``: decode steps (after the first token) whose
  expert layers computed the held experts in the dense form
  (``repro.models.moe.held_dense`` at the batch's tokens); 0 in a model
  without a dropless expert layer.

Take a snapshot before and after the work of interest and subtract:
the totals are shared by every engine in the process.
"""
from __future__ import annotations

_counts: dict[str, int] = {}


def add(name: str, n: int) -> None:
    _counts[name] = _counts.get(name, 0) + int(n)


def counters() -> dict[str, int]:
    """A snapshot of every total so far."""
    return dict(_counts)

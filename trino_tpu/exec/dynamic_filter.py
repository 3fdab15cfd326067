"""Dynamic filtering: build-side join keys prune probe-side scans.

The local analogue of the reference's DynamicFilterService
(server/DynamicFilterService.java:105 + operator/DynamicFilterSourceOperator.
java:44): when a hash-join build side finishes, its key domain (min/max +
exact distinct set when small) becomes an extra predicate on the probe-side
table scan.  Because pipelines execute in dependency order (build before
probe), the filter is always complete before the probe scan starts — the
in-process equivalent of Trino's lazy-blocking DynamicFilter futures.

Only INNER and RIGHT joins attach filters: their unmatched probe rows are
dropped anyway, so pre-filtering cannot change results.  LEFT/FULL/SINGLE
joins and semi-join marks must see every probe row.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

__all__ = ["DynamicFilterHolder", "MAX_DISTINCT_SET"]

MAX_DISTINCT_SET = 1 << 16  # keep an exact value set up to this many keys


class DynamicFilterHolder:
    """One build-side key column's domain, filled at JoinBuildSink.finish."""

    def __init__(self):
        self.ready = False
        self.empty = False  # build side had no rows: nothing can match
        self.vmin = None
        self.vmax = None
        self.values: Optional[np.ndarray] = None  # sorted exact set (or None)
        self.dict_values: Optional[set] = None  # for dictionary columns
        self.has_nan = False  # build had NaN keys (NaN joins NaN here)
        self.rows_pruned = 0  # observability: how many probe rows we dropped
        # device-resident domain, materialized on first probe_mask use (a
        # blocking fetch at fill time stalls the build's dispatch pipeline
        # and buys nothing when every probe batch is device-pinned)
        self._pending_device = None

    def fill_device(self, data, valid, live,
                    dictionary: Optional[np.ndarray]) -> None:
        """Device-resident build keys: derive the domain with ONE jitted
        program + one small device_get instead of pulling the key column to
        host (the round-3 fill cost a full D2H of the build keys).  Builds
        small enough for an exact value set (<= MAX_DISTINCT_SET rows) pull
        the keys in one round trip and keep :meth:`fill`'s exact-set
        pruning; larger builds degrade to min/max range (+ dictionary
        presence for string keys)."""
        import jax

        n = int(data.shape[0])
        host_like = isinstance(data, np.ndarray) and (
            valid is None or isinstance(valid, np.ndarray)) and (
            live is None or isinstance(live, np.ndarray))
        if host_like or (n <= MAX_DISTINCT_SET and dictionary is None):
            from . import syncguard as SG

            data, valid, live = SG.fetch((data, valid, live),
                                         "dynfilter.build-domain")
            if live is not None:
                keep = np.asarray(live)
                data = np.asarray(data)[keep]
                valid = None if valid is None else np.asarray(valid)[keep]
            self.fill(np.asarray(data), valid, dictionary)
            return
        from .kernels import _device_domain

        dict_len = len(dictionary) if dictionary is not None else 0
        out = _device_domain(data, valid, live, dict_len)
        for a in jax.tree_util.tree_leaves(out):
            try:  # start the transfer; the sync happens lazily if ever
                a.copy_to_host_async()
            # tpulint: disable=error-taxonomy -- async-copy is a hint; backends without it keep the lazy fetch
            except Exception:
                pass
        self._pending_device = (out, dictionary)
        self.ready = True

    def _materialize(self) -> None:
        """Pull the device-computed domain to host (first probe_mask use)."""
        import jax

        out, dictionary = self._pending_device
        self._pending_device = None
        from . import syncguard as SG

        cnt, cnt_nonnan, vmin, vmax, presence = SG.fetch(
            out, "dynfilter.materialize")
        if int(cnt) == 0:
            self.empty = True
            return
        if dictionary is not None:
            self.dict_values = set(
                str(v) for v in dictionary[np.asarray(presence)])
        else:
            self.has_nan = int(cnt_nonnan) < int(cnt)
            if int(cnt_nonnan) > 0:
                self.vmin = vmin
                self.vmax = vmax

    def fill(self, data: np.ndarray, valid: Optional[np.ndarray],
             dictionary: Optional[np.ndarray]) -> None:
        data = np.asarray(data)
        if valid is not None:
            data = data[np.asarray(valid)]
        if data.size == 0:
            self.empty = True
            self.ready = True
            return
        if dictionary is not None:
            # dictionary codes are per-batch namespaces: keep the VALUES
            self.dict_values = set(str(v) for v in dictionary[np.unique(data)])
        else:
            uniq = np.unique(data)
            if np.issubdtype(uniq.dtype, np.floating):
                # NaN would poison the min/max range (x <= NaN is always
                # False); the engine's join kernels treat NaN = NaN as a
                # match, so remember it separately
                self.has_nan = bool(np.isnan(uniq).any())
                uniq = uniq[~np.isnan(uniq)]
                if uniq.size == 0:
                    if not self.has_nan:
                        self.empty = True
                    self.ready = True
                    return
            self.vmin = uniq[0]
            self.vmax = uniq[-1]
            if uniq.size <= MAX_DISTINCT_SET:
                self.values = uniq
        self.ready = True

    def probe_mask(self, data: np.ndarray, valid: Optional[np.ndarray],
                   dictionary: Optional[np.ndarray]) -> Optional[np.ndarray]:
        """Row mask of possibly-matching probe rows (None = keep all).
        NULL keys never match an equi-join, so they are dropped too."""
        if not self.ready:
            return None
        if self._pending_device is not None:
            self._materialize()
        data = np.asarray(data)
        if self.empty:
            return np.zeros(data.shape[0], bool)
        if dictionary is not None:
            if self.dict_values is None:
                return None
            code_ok = np.array([str(v) in self.dict_values for v in dictionary])
            mask = code_ok[data] if len(code_ok) else np.zeros(data.shape[0], bool)
        elif self.values is not None:
            pos = np.searchsorted(self.values, data)
            clipped = np.minimum(pos, self.values.size - 1)
            mask = self.values[clipped] == data
        elif self.vmin is not None:
            mask = (data >= self.vmin) & (data <= self.vmax)
        else:
            return None
        if self.has_nan and np.issubdtype(data.dtype, np.floating):
            mask = mask | np.isnan(data)
        if valid is not None:
            mask = mask & np.asarray(valid)
        return mask

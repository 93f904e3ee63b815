"""The clients as the server sees them: a batcher bank that counts.

``NeuLiteServer`` takes, in place of a list of data sets, any indexable
object with ``bank[cid] -> Batcher`` and ``len(bank)``.  This bank holds one
``CountingBatcher`` per client over a shared image array, so no client's
data is copied, and every batch a batcher makes is counted.  The round
stacker pads a short cohort by repeating a batch it already made, so padding
is never counted: the counts are the real local steps and samples.

While ``record`` is on, the bank also logs ``(cid, indices)`` of every batch
in the order they were made, which is the order ``stack_round`` walks the
cohort; the plain reference replays the rounds from that log.
"""
from __future__ import annotations

import numpy as np

from repro.data.loader import Batcher


class _ClientData:
    """Just enough of a data set for ``Batcher``: its true size."""

    def __init__(self, index: np.ndarray):
        self.index = index

    def __len__(self) -> int:
        return len(self.index)


class CountingBatcher(Batcher):
    def __init__(self, bank: "CountingBank", cid: int, index: np.ndarray,
                 batch_size: int, seed):
        super().__init__(_ClientData(index), batch_size, seed=seed,
                         kind="image")
        self.bank = bank
        self.cid = cid

    def make_batch(self, idx):
        bank = self.bank
        rows = self.ds.index[idx]
        bank.round_steps[self.cid] = bank.round_steps.get(self.cid, 0) + 1
        bank.round_samples += len(rows)
        if bank.record:
            bank.log.append((self.cid, rows.copy()))
        return {"inputs": {"images": bank.images[rows]},
                "labels": bank.labels[rows]}


class CountingBank:
    def __init__(self, images: np.ndarray, labels: np.ndarray, parts,
                 batch_size: int, seed: int):
        self.images = images
        self.labels = labels
        self.batchers = [
            CountingBatcher(self, cid, np.asarray(p), batch_size,
                            seed=[seed, cid])
            for cid, p in enumerate(parts)]
        self.record = False
        self.log: list = []
        self.round_steps: dict = {}
        self.round_samples = 0

    def __len__(self) -> int:
        return len(self.batchers)

    def __getitem__(self, cid) -> CountingBatcher:
        return self.batchers[int(cid)]

    def take_round(self):
        """(real steps per cohort, real samples) made since the last call."""
        steps, samples = list(self.round_steps.values()), self.round_samples
        self.round_steps, self.round_samples = {}, 0
        return steps, samples

    def take_log(self) -> list:
        log, self.log = self.log, []
        return log

"""Driver of the admission cell: one full block of signatures after another
through the public ``crypto.admission.admit_batch``, closed loop, each call
ending with the result on the host. No chain around it."""

from __future__ import annotations

import time

import numpy as np

from benchmark.generators.signed_payloads import Corpus


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, spans):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.spans = spans
        self.lanes_per_block = int(traffic["lanes"])
        self.series: list[dict] = []
        self.results: list[tuple[int, tuple]] = []  # (rotation, admit_batch's answer)
        self.setup_parts: dict[str, float] = {}
        self.attempted = 0

    def setup(self, seconds: float) -> None:
        from fisco_bcos_tpu.crypto import admission

        self._admit = admission.admit_batch
        t = time.monotonic()
        self.corpus = Corpus(self.traffic, self.seed)
        self.setup_parts["corpus_s"] = time.monotonic() - t
        t = time.monotonic()
        self._block(0, keep=False)  # traces, loads or compiles the one shape
        self.setup_parts["admission_program_s"] = time.monotonic() - t
        t = time.monotonic()
        self._block(1, keep=False)
        self.setup_parts["warm_batches_s"] = time.monotonic() - t

    def _block(self, k: int, keep: bool = True):
        rot = k % len(self.corpus.blocks)
        block = self.corpus.blocks[rot]
        with self.spans.span("bench.admit_batch"):
            out = self._admit(block["payloads"], block["sigs"])
        if keep:
            self.results.append((rot, out))
        return out

    def _run(self, seconds: float, blocks: int | None) -> None:
        t0 = time.perf_counter()
        k = 0
        while (k < blocks) if blocks is not None else (time.perf_counter() - t0 < seconds):
            t = time.perf_counter()
            self._block(len(self.series))
            self.series.append({"k": k, "block_ms": (time.perf_counter() - t) * 1e3})
            k += 1

    def window(self, seconds: float) -> None:
        self.t0 = time.perf_counter()
        self._run(seconds, None)
        self.t1 = time.perf_counter()
        self.window_blocks = len(self.series)
        self.attempted = self.window_blocks * self.lanes_per_block

    def traced(self, blocks: int) -> None:
        n = len(self.series)
        self._run(0.0, blocks)
        self.traced_series, self.series = self.series[n:], self.series[:n]

    def end_to_end(self) -> dict:
        return {"verify_tps": self.attempted / (self.t1 - self.t0)}

    # -- correct -------------------------------------------------------------

    def after_window(self) -> None:
        block, self.corrupt_lanes = self.corpus.corrupted()
        with self.spans.span("bench.admit_batch"):
            self.corrupt_out = self._admit(block["payloads"], block["sigs"])
        self.corrupt_idx = block["idx"]

    def observe(self) -> dict:
        def plain(out):
            sender, ok, pub, digest = (np.asarray(a) for a in out)
            return {"sender": sender.astype(np.uint8), "ok": ok.astype(bool),
                    "pub": pub.astype(np.uint8), "digest": digest.astype(np.uint8)}

        return {
            "blocks": [(rot, plain(out)) for rot, out in self.results],
            "corrupt": plain(self.corrupt_out),
        }

    def _lanes_off(self, got: dict, idx, skip=()) -> int:
        """Lanes on which the answer differs from what the generator knows by
        construction: valid, and sender, key and digest of the signer."""
        want = self.corpus.unique
        bad = ~got["ok"]
        for key in ("sender", "pub", "digest"):
            bad |= (got[key] != want[key][idx]).any(axis=1)
        bad[list(skip)] = False
        return int(bad.sum())

    def compare(self, seen: dict) -> list[dict]:
        off = sum(
            self._lanes_off(got, self.corpus.blocks[rot]["idx"]) for rot, got in seen["blocks"]
        )
        corrupt = seen["corrupt"]
        accepted = int(corrupt["ok"][self.corrupt_lanes].sum())
        # the digest of a rejected lane is still the payload's
        digest_off = int((corrupt["digest"] != self.corpus.unique["digest"][self.corrupt_idx]).any(axis=1).sum())
        return [
            {"name": "lanes_differing_from_construction", "value": off, "limit": 0},
            {"name": "corrupted_lanes_accepted", "value": accepted, "limit": 0},
            {"name": "valid_lanes_wrong_beside_corrupted",
             "value": self._lanes_off(corrupt, self.corrupt_idx, skip=self.corrupt_lanes), "limit": 0},
            {"name": "digests_differing_in_corrupted_block", "value": digest_off, "limit": 0},
        ]

    def controls(self) -> dict:
        def accepted_corrupt(seen):  # a lane with r = 0 admitted
            seen["corrupt"]["ok"][self.corrupt_lanes[0]] = True

        def truncated_digest(seen):  # digests cut to 16 bytes
            for _rot, got in seen["blocks"]:
                got["digest"][:, 16:] = 0

        def wrong_sender(seen):  # one lane of one block answered with its neighbour's sender
            _rot, got = seen["blocks"][self.seed % len(seen["blocks"])]
            lane = self.seed % (self.lanes_per_block - 1)
            got["sender"][lane] = got["sender"][lane + 1]

        return {
            "accepted_corrupt": accepted_corrupt, "truncated_digest": truncated_digest,
            "wrong_sender": wrong_sender,
        }

    def failed_count(self) -> int:
        return sum(int((~np.asarray(out[1])).sum()) for _rot, out in self.results)

    def close(self) -> None:
        pass

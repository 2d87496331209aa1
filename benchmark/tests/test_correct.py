"""``correct`` comes out false when a guarantee is broken: the cell's
control (what ``control.py`` runs on the chip at the cell's own size),
and the timed path broken underneath a whole run."""

import pytest

from benchmark import run

CELLS = ["restic-10g.backup", "smallfiles.backup", "fleet-100.stream"]


def _rehearse(cell, seed, fault=None, seconds=1.0):
    return run.run_cell(cell, seed, seconds, False, size="rehearsal",
                        fault=fault)


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(cell):
    """One bit flipped in one stored pack of one operation drawn from
    the seed, after the window, or one bit flipped in the sampled
    streams' payload on its way to the service."""
    fault = run.find_cell(cell)[2]["control"]
    broken = _rehearse(cell, 3000000019, fault)
    assert broken["correct"] is False
    assert broken["failed"] > 0 and broken["attempted"] > 0


@pytest.mark.parametrize("seed", [5, 6, 7, 8])
def test_the_control_is_caught_in_whichever_operation_it_falls(seed, capsys):
    """Several operations a window: the flipped pack is in one the seed
    draws, and every operation is read back."""
    broken = _rehearse("smallfiles.backup", seed, "flip_pack_bit", 3.0)
    assert broken["correct"] is False and broken["failed"] == 1
    out = capsys.readouterr().out
    assert '"fault": "flip_pack_bit"' in out


def test_a_sound_run_is_correct():
    sound = _rehearse("restic-10g.backup", 3000000019)
    assert sound["correct"] is True and sound["failed"] == 0


def test_backup_with_an_id_altered_where_it_is_produced(monkeypatch):
    """A worker that hands the tree another id than the one its blob
    was stored under, as a faster and wrong hash would."""
    from volsync_tpu.engine import backup

    real = backup.TreeBackup._hash_file
    altered = []

    def wrong_once(self, path, rel, st, stats):
        rel, (content, size, mtime) = real(self, path, rel, st, stats)
        if content and rel.endswith("f00000"):
            d = content[0]
            content = [d[:-1] + ("0" if d[-1] != "0" else "1")] + content[1:]
            altered.append(rel)
        return rel, (content, size, mtime)

    monkeypatch.setattr(backup.TreeBackup, "_hash_file", wrong_once)
    broken = _rehearse("smallfiles.backup", 11)
    assert altered and broken["correct"] is False


def test_stream_with_a_digest_altered_where_it_is_produced(monkeypatch):
    """The batched segment program's host driver answers every lane;
    alter the digests it hands to the service."""
    from volsync_tpu.ops import segment

    real = segment.BatchedSegmentHasher.hash_segments

    def wrong(self, items):
        return [([(s, n, d[:-1] + ("0" if d[-1] != "0" else "1"))
                  for s, n, d in chunks], consumed)
                for chunks, consumed in real(self, items)]

    monkeypatch.setattr(segment.BatchedSegmentHasher, "hash_segments", wrong)
    broken = _rehearse("fleet-100.stream", 11)
    assert broken["correct"] is False
    assert broken["failed"] > 0


def test_stream_cut_where_the_reference_does_not(monkeypatch):
    """A segment program that cuts a stream's first chunk in two, each
    half with its true digest: coverage and digests hold, the cuts do
    not."""
    from benchmark.reference import blobid
    from volsync_tpu.ops import segment

    real = segment.BatchedSegmentHasher.hash_segments

    def split(self, items):
        out = []
        for (buf, _, _), (chunks, consumed) in zip(items, real(self, items)):
            if chunks and chunks[0][1] > 8192:
                s0, n0, _ = chunks[0]
                view = memoryview(buf)
                chunks = [
                    (s0, 4096, blobid.blob_id(view[s0: s0 + 4096])),
                    (s0 + 4096, n0 - 4096,
                     blobid.blob_id(view[s0 + 4096: s0 + n0])),
                ] + list(chunks[1:])
            out.append((chunks, consumed))
        return out

    monkeypatch.setattr(segment.BatchedSegmentHasher, "hash_segments", split)
    broken = _rehearse("fleet-100.stream", 11)
    assert broken["correct"] is False
    assert broken["failed"] > 0

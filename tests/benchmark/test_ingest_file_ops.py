"""``share.ingest``'s file operations a conversation, pinned in tier-1 (PR 40):
one conversation of a new tenant is 33 — ``switch_user`` 0 + 4,
``add_nodes_columns`` 5, the consolidation's save 10, the decay's save 7, the
journals 7 — where it was 59 while every store write re-read and re-wrote
``VERSION``, asked its segments' footers for their row counts and wrote
sidecars the file already held. A later tree that adds a read-before-write
shows here and not only on the chip. A CPU debug run at a tiny size: the
count is the program's, no number read here is a device number."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, HERE]

import contracts  # noqa: E402


def test_a_conversation_is_at_most_34_file_operations():
    res = contracts.debug_run("share.ingest", 2**31 + 40, ROOT, traced=True)
    assert res["correct"] is True and res["failed"] == 0 < res["attempted"]
    ops = res["metrics"]["store.file_ops_per_conv"]["value"]
    assert ops == int(ops) and 0 < ops <= 34

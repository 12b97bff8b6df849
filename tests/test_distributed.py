"""Distributed (mesh) path tests on the virtual 8-device CPU mesh —
the Ring-2 pattern: no pod required (SURVEY.md section 4)."""

import jax
import numpy as np
import pytest

from spark_rapids_tpu.parallel.distributed import (
    dryrun_distributed_q1, dryrun_session_mesh,
)


def test_dryrun_distributed_q1_8dev():
    assert len(jax.devices()) >= 8
    dryrun_distributed_q1(8)


def test_dryrun_distributed_q1_2dev():
    dryrun_distributed_q1(2, rows_per_shard=256)


def test_session_mesh_4dev():
    """Join+agg, global sort, LIMIT over the sort and a broadcast join
    through the session on a 4-device mesh, each against the CPU oracle.
    The limit case is the one every TPC-H LIMIT query (q2, q3, q10, q18)
    failed on: its running count was committed to the first partition's
    device."""
    dryrun_session_mesh(4)

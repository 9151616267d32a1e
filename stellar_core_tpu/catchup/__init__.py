"""Catchup from history archives (reference: src/catchup)."""

from .apply_buckets import ApplyBucketsWork
from .catchup_work import (CATCHUP_COMPLETE, CATCHUP_MINIMAL,
                           ApplyCheckpointWork, CatchupConfiguration,
                           CatchupWork, GetHistoryArchiveStateWork,
                           GetRemoteFileWork)

__all__ = ["CatchupWork", "CatchupConfiguration", "ApplyCheckpointWork",
           "ApplyBucketsWork", "GetRemoteFileWork",
           "GetHistoryArchiveStateWork", "CATCHUP_COMPLETE",
           "CATCHUP_MINIMAL"]

from repro.serving.pipeline import QueryPipeline
from repro.serving.retrieval import RetrievalService, embed_texts
from repro.serving.service import (BatchRecord, PendingQuery,
                                   ServiceStats, ShardedLSHService)
from repro.serving.workers import (AdmissionFull, AsyncLSHService,
                                   AsyncQuery, AsyncWrite)

__all__ = ["RetrievalService", "embed_texts", "ShardedLSHService",
           "ServiceStats", "PendingQuery", "BatchRecord", "QueryPipeline",
           "AsyncLSHService", "AsyncQuery", "AsyncWrite",
           "AdmissionFull"]

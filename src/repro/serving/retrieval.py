"""Retrieval serving path: LM embeddings + the paper's distributed LSH.

This is the paper's workload with the model zoo as the feature extractor:
  index build: embed documents -> DistributedLSHIndex.build (one routed
               row per doc, Fig 3.2 preprocessing);
  streaming:   embed new documents -> ShardedLSHService.insert (routed
               append into the per-shard regions);
  query:       embed query -> ShardedLSHService micro-batch -> entropy
               offsets -> Layered-LSH route -> per-shard bucket search
               -> (c,r)-NN results.

Embeddings are mean-pooled final hidden states, l2-normalised (so the
paper's Wiki/Image unit-norm setting applies directly).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DistributedLSHIndex, LSHConfig, Scheme
from repro.models.config import ModelConfig
from repro.models.layers import embed as embed_tokens
from repro.models.transformer import _apply_segment  # reuse blocks
from repro.serving.service import ShardedLSHService
from repro.serving.workers import AsyncLSHService, AsyncWrite


def embed_texts(params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    """Mean-pooled final hidden state, unit norm. tokens: (B, S)."""
    x = embed_tokens(params["embed"], tokens).astype(cfg.cdtype)
    for seg, sp in zip(cfg.segments, params["segments"]):
        x, _, _ = _apply_segment(sp, seg, cfg, x, pos0=0, cache=None,
                                 remat=False)
    pooled = x.mean(axis=1).astype(jnp.float32)
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


@dataclasses.dataclass
class RetrievalService:
    """End-to-end embed->route->search service over a device mesh."""
    cfg: ModelConfig
    lsh: LSHConfig
    params: dict
    index: DistributedLSHIndex
    service: "ShardedLSHService | AsyncLSHService"

    @classmethod
    def build(cls, cfg: ModelConfig, params, doc_tokens, mesh,
              r: float = 0.25, c: float = 2.0, k: int = 10, L: int = 16,
              W: float = 1.0, scheme: Scheme = Scheme.LAYERED,
              seed: int = 0, use_kernel: "bool | None" = None,
              bucket_size: int = 64, max_latency_ms: float = 25.0,
              k_neighbors: int = 1, n_tables: int = 1,
              pipelined: bool = False):
        """n_tables > 1 fuses that many independent hash tables into the
        one routed index (the classic recall lever) at NO extra
        collectives per query -- only extra rows inside the same ones.

        pipelined=True serves through ``AsyncLSHService`` (double-
        buffered query pipeline + worker threads, bitwise-identical
        results); the default stays the synchronous micro-batcher.

        use_kernel=None searches with the Pallas kernels on an
        accelerator and the jnp oracle on the CPU (see
        ``DistributedLSHIndex``)."""
        docs = embed_texts(params, cfg, doc_tokens)
        lsh = LSHConfig(d=int(docs.shape[1]), k=k, W=W, r=r, c=c, L=L,
                        n_shards=mesh.shape["shard"], scheme=scheme,
                        seed=seed, n_tables=n_tables)
        index = DistributedLSHIndex(lsh, mesh, use_kernel=use_kernel,
                                    k_neighbors=k_neighbors)
        index.build(docs)
        front = AsyncLSHService if pipelined else ShardedLSHService
        service = front(index, bucket_size=bucket_size,
                        max_latency_ms=max_latency_ms,
                        k_neighbors=k_neighbors)
        return cls(cfg=cfg, lsh=lsh, params=params, index=index,
                   service=service)

    @classmethod
    def recover_or_build(cls, cfg: ModelConfig, params, doc_tokens, mesh, *,
                         snapshot_dir: "str | None" = None,
                         bucket_size: int = 64,
                         max_latency_ms: float = 25.0,
                         k_neighbors: int = 1, pipelined: bool = False,
                         **build_kwargs):
        """The durable entry point shared by the serve drivers.

        With a ``snapshot_dir`` holding a snapshot: warm-restart (restore
        + WAL-tail replay through a WAL-attached service) and skip the
        embed+build entirely.  Otherwise build fresh from ``doc_tokens``
        and, when a ``snapshot_dir`` is given, attach a WriteAheadLog and
        write the boot snapshot so the service is recoverable from its
        first streamed write.  Returns ``(service, RecoverResult|None)``
        -- the second element is None on a cold build.
        """
        from repro import persist
        if snapshot_dir and persist.has_snapshot(snapshot_dir):
            rr = persist.recover(
                snapshot_dir, mesh,
                service=dict(bucket_size=bucket_size,
                             max_latency_ms=max_latency_ms,
                             k_neighbors=k_neighbors))
            # a warm restart keeps the SNAPSHOT's LSHConfig (stored rows
            # were hashed under it); surface any build kwarg the caller
            # changed since, instead of silently serving the old config
            drift = {
                kw: (v, getattr(rr.index.cfg, kw))
                for kw, v in build_kwargs.items()
                if hasattr(rr.index.cfg, kw)
                and getattr(rr.index.cfg, kw) != v}
            if drift:
                import warnings
                warnings.warn(
                    f"warm restart from {snapshot_dir} keeps the "
                    f"snapshot's LSH config; ignoring changed flags "
                    f"{ {k: f'{want} (snapshot: {have})' for k, (want, have) in drift.items()} } "
                    f"-- rebuild without --snapshot-dir (or a fresh dir) "
                    f"to apply them", stacklevel=2)
            service = rr.service
            if pipelined:
                # replay ran through the recovered synchronous service;
                # serve through the pipelined front-end from here on,
                # carrying its stats (replay flush counts) and WAL
                service = AsyncLSHService(
                    rr.index, bucket_size=bucket_size,
                    max_latency_ms=max_latency_ms,
                    k_neighbors=k_neighbors, wal=rr.wal,
                    stats=rr.service.stats)
            svc = cls(cfg=cfg, lsh=rr.index.cfg, params=params,
                      index=rr.index, service=service)
            return svc, rr
        svc = cls.build(cfg, params, doc_tokens, mesh,
                        bucket_size=bucket_size,
                        max_latency_ms=max_latency_ms,
                        k_neighbors=k_neighbors, pipelined=pipelined,
                        **build_kwargs)
        if snapshot_dir:
            svc.service.wal = persist.WriteAheadLog(
                persist.wal_path(snapshot_dir))
            persist.snapshot(svc.index, snapshot_dir, wal=svc.service.wal)
        return svc, None

    def insert_docs(self, doc_tokens) -> "np.ndarray":
        """Embed and stream new documents into the index; returns gids."""
        if doc_tokens.shape[0] == 0:
            return np.empty((0,), np.int64)
        docs = embed_texts(self.params, self.cfg, doc_tokens)
        res = self.service.insert(docs)
        if isinstance(res, AsyncWrite):
            res = res.result()       # pipelined front-end returns a future
        if res.drops:
            # dropped rows are not the trailing ones, so the gid->doc
            # attribution below would silently lie -- refuse instead
            raise RuntimeError(
                f"insert overflow: {res.drops} of {docs.shape[0]} docs "
                f"dropped (store capacity {res.capacity}/shard)")
        return np.arange(res.gid_start, res.gid_start + res.n_inserted)

    def query(self, query_tokens) -> tuple[np.ndarray, np.ndarray, list]:
        """Embed a batch of queries and answer through the micro-batcher.

        Returns (b, K) top-K gid and distance arrays (K = the service's
        k_neighbors; column 0 is the best candidate) plus the handles.
        """
        q = embed_texts(self.params, self.cfg, query_tokens)
        handles = self.service.submit_batch(np.asarray(q))
        self.service.drain()
        gids = np.stack([h.gids for h in handles])
        dists = np.stack([h.dists for h in handles])
        return gids, dists, handles

    def close(self) -> None:
        """Drain and stop a pipelined service (no-op for the sync one)."""
        if isinstance(self.service, AsyncLSHService):
            self.service.close()

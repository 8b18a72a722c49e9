"""ANN serving end to end: index build -> batched query service -> metrics.

    PYTHONPATH=src python examples/ann_serving.py

Thin wrapper over launch/serve.py (the serving driver) with a smaller
default corpus.  The service runs the same staged SearchPipeline as offline
search and serves ANY AnnIndex — swap ``--method`` for lsh / kdtree /
bruteforce; on a pod the identical service runs over the sharded index
(core/distributed.py + serve/ann_service.py).  ``stats()`` reports the
service's own p50/p99 batch latency from its wall-time histogram.
"""
from repro.launch import serve


def main():
    out = serve.main([
        "--n-docs", "50000", "--queries", "256", "--batch", "64", "--q", "50",
    ])
    assert out["recall@k"] > 0.9  # depth-100 + rerank on 50k docs
    assert out["p50_ms_per_batch"] is not None  # latency histogram filled


if __name__ == "__main__":
    main()

"""Deploy + serve: export a trained model and serve it from a predictor
pool across worker threads.

Reference workflow: train → `paddle.jit.save` → paddle_inference
`Config`/`create_predictor` per thread via `AnalysisPredictor::Clone` /
`services::PredictorPool` (fluid/inference/api/paddle_inference_api.h).
TPU-native: the artifact is an executable StableHLO module (AOT-compiled
once); clones share the immutable executable — XLA replaces the
reference's per-clone analysis-pass pipeline — and each pool member owns
its IO handles so worker threads never race.
"""
import concurrent.futures
import os
import tempfile
import time

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.inference import (Config, DeadlineExceeded, Overloaded,
                                  PredictorPool, ServingPool)

SMOKE = os.environ.get("EXAMPLES_SMOKE") == "1"


def train_model(rng):
    X = rng.randn(256, 16).astype("float32")
    W = rng.randn(16, 4).astype("float32")
    y = np.argmax(X @ W, axis=1).astype("int64")
    model = nn.Sequential(nn.Linear(16, 64), nn.ReLU(), nn.Linear(64, 4))
    opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                 parameters=model.parameters())
    loss_fn = nn.CrossEntropyLoss()
    for _ in range(15 if SMOKE else 80):
        loss = loss_fn(model(paddle.to_tensor(X)), paddle.to_tensor(y))
        loss.backward()
        opt.step()
        opt.clear_grad()
    model.eval()
    return model, X, y


def main():
    paddle.seed(0)
    rng = np.random.RandomState(0)
    model, X, y = train_model(rng)

    with tempfile.TemporaryDirectory(prefix="serve_") as tmp:
        path = os.path.join(tmp, "infer")
        _serve(model, X, y, path)
        _serve_resilient(X, y, path)
        _serve_batched(model, X, os.path.join(tmp, "infer1"))


def _serve(model, X, y, path):
    # export the deploy artifact (fixed serving batch of 8)
    spec = paddle.to_tensor(np.zeros((8, 16), np.float32))
    paddle.jit.save(model, path, input_spec=[spec])

    # serve: 4-member pool; each request leases a member exclusively
    # (pool.acquire()) — with a dynamically-scheduled thread pool, fixed
    # index retrieval could put two in-flight requests on one member
    pool = PredictorPool(Config(path), size=4)
    requests = [X[i:i + 8] for i in range(0, 128, 8)]

    def serve(i):
        with pool.acquire() as p:
            h = p.get_input_handle(p.get_input_names()[0])
            h.copy_from_cpu(requests[i])
            (logits,) = p.run()
        return i, logits.argmax(-1)

    preds = np.empty(128, np.int64)
    with concurrent.futures.ThreadPoolExecutor(max_workers=4) as ex:
        for i, cls in ex.map(serve, range(len(requests))):
            preds[i * 8:(i + 1) * 8] = cls

    acc = float((preds == y[:128]).mean())
    print(f"served {len(requests)} requests across 4 threads; "
          f"accuracy {acc:.3f}")
    assert acc > 0.8, acc


def _serve_resilient(X, y, path):
    """Production traffic wants more than exclusive leases: deadlines that
    cover queue wait + execution, and load shedding instead of unbounded
    queueing. ServingPool (docs/serving.md) adds both, plus member
    supervision (re-clone on failure, circuit breaker, hang detection)."""
    # generous default deadline: the first request pays the one-off XLA
    # compile of the loaded module, which a loaded CI box can stretch
    pool = ServingPool(Config(path), size=2, max_queue_depth=2,
                       default_timeout=30.0)

    # normal traffic: infer() leases a healthy member and enforces the
    # deadline end-to-end, raising typed errors instead of hanging
    (logits,) = pool.infer([X[:8]])
    acc = float((logits.argmax(-1) == y[:8]).mean())
    print(f"resilient pool served a batch; accuracy {acc:.3f}")

    # deadline: a request admitted with no time budget left is refused
    # BEFORE any compute is wasted
    try:
        pool.infer([X[:8]], timeout=-1.0)
        raise AssertionError("expected DeadlineExceeded")
    except DeadlineExceeded:
        print("past-deadline request rejected before compute (typed)")

    # overload shedding: saturate both members with slow requests and
    # fill the 2-deep admission queue — further traffic is shed with
    # `Overloaded` instead of queueing unboundedly
    def slow(pred):
        time.sleep(0.3)
        return pred.run([X[:8]])

    in_flight = [pool.submit(slow) for _ in range(2)]   # occupy members
    time.sleep(0.05)
    backlog = [pool.submit(slow) for _ in range(2)]     # fill the queue
    shed = 0
    for _ in range(4):
        try:
            pool.submit(slow)
        except Overloaded:
            shed += 1
    for f in in_flight + backlog:
        f.result()
    stats = pool.stats()
    print(f"overload: {stats['admitted']} admitted, {stats['shed']} shed, "
          f"{stats['completed']} completed")
    assert shed == 4 and stats["shed"] >= 4

    # graceful drain: stop admissions, finish in-flight work, release
    drained = pool.shutdown(drain_timeout=5.0)
    print(f"drained cleanly: {drained}")
    assert drained


def _serve_batched(model, X, path):
    # -- dynamic request batching (docs/serving.md) ----------------------
    # single-example artifact: each request is one example; the pool
    # coalesces concurrent requests into bucketed batches and serves each
    # with ONE AOT dispatch, outputs bit-identical to unbatched execution
    from paddle_tpu.inference import BatchConfig

    paddle.jit.save(model, path, input_spec=[
        paddle.to_tensor(np.zeros((1, 16), np.float32))])
    pool = ServingPool(Config(path), size=2, default_timeout=10.0,
                       batching=BatchConfig(buckets=(1, 2, 4, 8),
                                            max_wait_ms=3.0))
    pool.warmup()   # compile (or disk-load) every bucket before traffic
    n = 16 if SMOKE else 64
    want = [model(paddle.to_tensor(X[i:i + 1])).numpy() for i in range(n)]
    with concurrent.futures.ThreadPoolExecutor(max_workers=8) as ex:
        outs = list(ex.map(
            lambda i: pool.infer([X[i:i + 1]])[0], range(n)))
    assert all((outs[i] == want[i]).all() for i in range(n))
    b = pool.stats()["batch"]
    print(f"batched: {b['requests']} requests in {b['formed']} dispatches "
          f"(occupancy {b['occupancy']:.2f}, by bucket "
          f"{b['executed_by_bucket']}, compile {b['compile']})")
    assert b["formed"] < n   # batching actually coalesced
    pool.shutdown(drain_timeout=5.0)


if __name__ == "__main__":
    main()

"""Serving tests — Processor/SessionGroup/ModelInstanceMgr behaviors
(reference: serving/processor tests, end2end/demo.cc flow: train a toy
model, serve it, hot-swap updates)."""
import threading

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deeprec_tpu.data import SyntheticCriteo
from deeprec_tpu.models import WDL
from deeprec_tpu.optim import Adagrad
from deeprec_tpu.serving import ModelServer, Predictor
from deeprec_tpu.training import Trainer
from deeprec_tpu.training.checkpoint import CheckpointManager


def J(b):
    return {k: jnp.asarray(v) for k, v in b.items()}


def make_trained(tmp_path, steps=5):
    model = WDL(emb_dim=8, capacity=1 << 12, hidden=(32,), num_cat=4, num_dense=2)
    tr = Trainer(model, Adagrad(lr=0.1), optax.adam(1e-3))
    st = tr.init(0)
    gen = SyntheticCriteo(batch_size=128, num_cat=4, num_dense=2, vocab=800, seed=21)
    batches = [J(gen.batch()) for _ in range(steps)]
    for b in batches:
        st, _ = tr.train_step(st, b)
    ck = CheckpointManager(str(tmp_path), tr)
    st, _ = ck.save(st)
    return model, tr, st, ck, batches, gen


def strip_labels(b):
    return {k: np.asarray(v) for k, v in b.items() if not k.startswith("label")}


def test_predictor_serves_and_matches_training_eval(tmp_path):
    model, tr, st, ck, batches, gen = make_trained(tmp_path)
    p = Predictor(model, str(tmp_path))
    probs = p.predict(strip_labels(batches[0]))
    _, expect = tr.eval_step(st, batches[0])
    np.testing.assert_allclose(np.asarray(probs), np.asarray(expect), atol=1e-6)
    info = p.model_info()
    assert info["step"] == 5 and all(v > 0 for v in info["table_sizes"].values())


def test_delta_model_update(tmp_path):
    model, tr, st, ck, batches, gen = make_trained(tmp_path)
    p = Predictor(model, str(tmp_path))
    before = p.predict(strip_labels(batches[0]))
    # train further, write only a DELTA
    for _ in range(3):
        st, _ = tr.train_step(st, batches[0])
    st, _ = ck.save_incremental(st)
    assert p.poll_updates() is True
    after = p.predict(strip_labels(batches[0]))
    assert p.step == 8
    _, expect = tr.eval_step(st, batches[0])
    np.testing.assert_allclose(np.asarray(after), np.asarray(expect), atol=1e-6)
    assert np.abs(np.asarray(after) - np.asarray(before)).max() > 1e-6
    # idempotent: nothing new
    assert p.poll_updates() is False


def test_full_model_update_supersedes(tmp_path):
    model, tr, st, ck, batches, gen = make_trained(tmp_path)
    p = Predictor(model, str(tmp_path))
    for _ in range(2):
        st, _ = tr.train_step(st, batches[1])
    st, _ = ck.save(st)  # new FULL checkpoint
    assert p.poll_updates() is True
    assert p.step == 7


def test_feature_store_read_through(tmp_path):
    """Keys missing from the device table serve the store's row instead of
    the initializer — Redis feature-store read-through parity
    (redis_feature_store.h:18)."""
    from deeprec_tpu.native import HostKV

    model, tr, st, ck, batches, gen = make_trained(tmp_path)
    # pick an id that was never trained: it misses in every table
    novel = 999_999
    req = strip_labels(batches[0])
    # stores keyed by table name; fill one table's store with a marked row
    tname = sorted(tr.tables)[0]
    dim = tr.tables[tname].cfg.dim
    kv = HostKV(dim=dim, initial_capacity=64)
    kv.put(np.asarray([novel], np.int64),
           np.full((1, dim), 2.5, np.float32),
           np.asarray([1], np.int32), np.asarray([1], np.int32))

    p_plain = Predictor(model, str(tmp_path))
    p_store = Predictor(model, str(tmp_path), stores={tname: kv})
    req_novel = dict(req)
    req_novel[tname] = np.full_like(req[tname], novel)
    out_plain = p_plain.predict(req_novel)
    out_store = p_store.predict(req_novel)
    # the store row changes the served prediction
    assert np.abs(np.asarray(out_store) - np.asarray(out_plain)).max() > 1e-6
    # and known keys predict identically through both paths
    np.testing.assert_allclose(
        np.asarray(p_store.predict(req)), np.asarray(p_plain.predict(req)),
        atol=1e-6,
    )


def test_http_server_end_to_end(tmp_path):
    """train -> save -> serve over HTTP -> delta-update -> prediction shifts
    (the round-1 review's acceptance flow for the serving frontend)."""
    import json
    import urllib.request

    from deeprec_tpu.serving import HttpServer

    model, tr, st, ck, batches, gen = make_trained(tmp_path)
    server = ModelServer(Predictor(model, str(tmp_path)), max_batch=64,
                         max_wait_ms=2)
    http = HttpServer(server, port=0).start()  # ephemeral port
    base = f"http://127.0.0.1:{http.port}"

    def call(path, payload=None):
        req = urllib.request.Request(
            base + path,
            data=None if payload is None else json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
            method="GET" if payload is None else "POST",
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())

    try:
        info = call("/v1/model_info")
        assert info["step"] == 5

        feats = {
            k: np.asarray(v)[:4].tolist()
            for k, v in strip_labels(batches[0]).items()
        }
        out1 = call("/v1/predict", {"features": feats})["predictions"]
        assert len(out1) == 4 and all(0.0 <= p <= 1.0 for p in out1)

        # delta-update: train on, save incremental, tell the server to poll
        for _ in range(3):
            st, _ = tr.train_step(st, batches[0])
        st, _ = ck.save_incremental(st)
        assert call("/v1/reload", {})["updated"] is True
        assert call("/v1/model_info")["step"] == 8
        out2 = call("/v1/predict", {"features": feats})["predictions"]
        assert np.abs(np.asarray(out2) - np.asarray(out1)).max() > 1e-6

        # inconsistent row counts -> 400 BEFORE batching (would otherwise
        # poison coalesced neighbors)
        ragged = {k: (v if i else v[:1]) for i, (k, v) in
                  enumerate(sorted(feats.items()))}
        req = urllib.request.Request(
            base + "/v1/predict",
            data=json.dumps({"features": ragged}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        try:
            urllib.request.urlopen(req, timeout=10)
            assert False, "expected HTTP 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert "row counts" in json.loads(e.read())["error"]

        # malformed requests -> 400 with a JSON error, server stays alive:
        # empty body, non-dict body, and a typo'd feature name (validated
        # BEFORE batching so it can't poison coalesced neighbors)
        bad_feats = dict(feats)
        bad_feats["C_TYPO"] = bad_feats.pop(sorted(feats)[0])
        for body in (b"{}", b"[1,2]",
                     json.dumps({"features": bad_feats}).encode()):
            req = urllib.request.Request(
                base + "/v1/predict", data=body,
                headers={"Content-Type": "application/json"}, method="POST",
            )
            try:
                urllib.request.urlopen(req, timeout=10)
                assert False, "expected HTTP 400"
            except urllib.error.HTTPError as e:
                assert e.code == 400
                err = json.loads(e.read())
                assert "error" in err
        hz = call("/healthz")
        assert hz["status"] == "ok"
        assert hz["consecutive_poll_failures"] == 0
        assert "staleness_seconds" in hz
    finally:
        http.stop()
        server.close()


def test_server_warmup_precompiles_buckets(tmp_path):
    model, tr, st, ck, batches, gen = make_trained(tmp_path)
    server = ModelServer(Predictor(model, str(tmp_path)), max_batch=32,
                         max_wait_ms=2)
    try:
        n = server.warmup(strip_labels(batches[0]))
        assert n == 3  # buckets 8, 16, 32
        out = server.request(
            {k: v[:1] for k, v in strip_labels(batches[0]).items()}
        )
        assert out.shape == (1,)
    finally:
        server.close()


def test_checkpoint_option_drops_filtered_features():
    """CheckpointOption(save_filtered_features=False): sub-threshold keys
    are dropped at export (TF_EV_SAVE_FILTERED_FEATURES parity); the
    default keeps them so admission counters survive restarts."""
    import dataclasses

    from deeprec_tpu import (
        CheckpointOption,
        CounterFilter,
        EmbeddingTable,
        EmbeddingVariableOption,
        TableConfig,
    )
    from deeprec_tpu.training.checkpoint import _state_to_np, export_table_arrays

    cfg = TableConfig(
        name="cf", dim=4, capacity=128,
        ev=EmbeddingVariableOption(counter_filter=CounterFilter(filter_freq=3)),
    )
    t = EmbeddingTable(cfg)
    s = t.create()
    hot = jnp.arange(5, dtype=jnp.int32)
    for step in range(3):
        s, _ = t.lookup_unique(s, hot, step=step)  # freq 3: admitted
    s, _ = t.lookup_unique(s, jnp.arange(5, 20, dtype=jnp.int32), step=3)

    keep_all = export_table_arrays(t, _state_to_np(s), only_dirty=False)
    assert keep_all["keys"].shape[0] == 20  # default: everything saved

    t2 = EmbeddingTable(dataclasses.replace(
        cfg, ev=dataclasses.replace(
            cfg.ev, ckpt=CheckpointOption(save_filtered_features=False))))
    shrunk = export_table_arrays(t2, _state_to_np(s), only_dirty=False)
    assert sorted(shrunk["keys"].tolist()) == list(range(5))
    assert (shrunk["freqs"] >= 3).all()


def test_remote_feature_store_over_tcp(tmp_path):
    """Predictor read-through against a REMOTE store (redis_feature_store
    parity): rows served over the network change predictions exactly like
    an in-process HostKV store."""
    from deeprec_tpu.native import HostKV
    from deeprec_tpu.serving import RemoteKVClient, RemoteKVServer

    model, tr, st, ck, batches, gen = make_trained(tmp_path)
    tname = sorted(tr.tables)[0]
    dim = tr.tables[tname].cfg.dim
    kv = HostKV(dim=dim, initial_capacity=64)
    srv = RemoteKVServer(kv, dim=dim).start()
    try:
        client = RemoteKVClient("127.0.0.1", srv.port, dim=dim)
        novel = 424242
        client.put(np.asarray([novel], np.int64),
                   np.full((1, dim), 1.75, np.float32))
        # round-trip sanity straight through the wire
        vals, _, _, found = client.get(np.asarray([novel, 77], np.int64))
        assert found.tolist() == [True, False]
        np.testing.assert_allclose(vals[0], 1.75)

        p_remote = Predictor(model, str(tmp_path), stores={tname: client})
        p_plain = Predictor(model, str(tmp_path))
        req = strip_labels(batches[0])
        req_novel = dict(req)
        req_novel[tname] = np.full_like(req[tname], novel)
        out_r = p_remote.predict(req_novel)
        out_p = p_plain.predict(req_novel)
        assert np.abs(np.asarray(out_r) - np.asarray(out_p)).max() > 1e-6
        # known keys unaffected
        np.testing.assert_allclose(
            np.asarray(p_remote.predict(req)),
            np.asarray(p_plain.predict(req)), atol=1e-6,
        )
        client.close()
    finally:
        srv.stop()


def test_http_serves_ragged_histories_one_shape(tmp_path):
    """Sequence models over HTTP: ragged JSON history lists pad/trim to the
    feature's declared max_len with its pad_value — one compiled shape per
    feature, and short histories predict fine."""
    from deeprec_tpu.data import SyntheticBehaviorSequence
    from deeprec_tpu.models import DIN
    from deeprec_tpu.serving import HttpServer
    import json
    import urllib.request

    model = DIN(emb_dim=4, capacity=1 << 10, hidden=(8,))
    tr = Trainer(model, Adagrad(lr=0.1), optax.adam(1e-3))
    st = tr.init(0)
    gen = SyntheticBehaviorSequence(batch_size=64, vocab=500, seq_len=6,
                                    seed=0)
    for _ in range(2):
        st, _ = tr.train_step(st, J(gen.batch()))
    ck = CheckpointManager(str(tmp_path), tr)
    st, _ = ck.save(st)

    server = ModelServer(Predictor(model, str(tmp_path)), max_batch=16,
                         max_wait_ms=2)
    http = HttpServer(server, port=0).start()
    try:
        feats = {
            "user": [1, 2],
            "target_item": [3, 4],
            "target_cat": [5, 6],
            "hist_items": [[7, 8, 9], [10]],  # ragged
            "hist_cats": [[1, 2, 3], [4]],
        }
        req = urllib.request.Request(
            f"http://127.0.0.1:{http.port}/v1/predict",
            data=json.dumps({"features": feats}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json.loads(r.read())["predictions"]
        assert len(out) == 2 and all(0.0 <= p <= 1.0 for p in out)
    finally:
        http.stop()
        server.close()


def test_model_server_batches_concurrent_requests(tmp_path):
    model, tr, st, ck, batches, gen = make_trained(tmp_path)
    server = ModelServer(Predictor(model, str(tmp_path)), max_batch=64,
                         max_wait_ms=5)
    req = strip_labels(batches[0])
    single = {k: v[:1] for k, v in req.items()}
    results = [None] * 16

    def call(i):
        results[i] = server.request(single)

    threads = [threading.Thread(target=call, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    server.close()
    assert all(r is not None and r.shape == (1,) for r in results)
    # all identical inputs -> identical outputs
    vals = np.asarray([float(r[0]) for r in results])
    np.testing.assert_allclose(vals, vals[0], atol=1e-6)


def test_model_server_coalesces_grouped_requests(tmp_path):
    """N-candidate user-tower reuse THROUGH the micro-batcher: concurrent
    `<user, N items>` requests marked group_users coalesce into one
    device batch whose user tower runs once per distinct user across ALL
    of them; outputs are row-identical to direct predicts, every request
    is stamped with the one version its shared batch served from, and a
    plain request arriving in the middle never shares their dispatch."""
    import optax as _optax

    from deeprec_tpu.data import SyntheticTwoTower
    from deeprec_tpu.models import DSSM

    model = DSSM(emb_dim=8, capacity=1 << 12, num_user_feats=2,
                 num_item_feats=2, hidden=(32, 16))
    tr = Trainer(model, Adagrad(lr=0.1), _optax.adam(2e-3))
    st = tr.init(0)
    gen = SyntheticTwoTower(batch_size=128, num_user=2, num_item=2,
                            vocab=500, seed=31)
    for _ in range(3):
        st, _ = tr.train_step(st, J(gen.batch()))
    CheckpointManager(str(tmp_path), tr).save(st)
    pred = Predictor(model, str(tmp_path))
    base = strip_labels(gen.batch())

    def user_req(u, n_items=8):
        out = {}
        for k, v in base.items():
            rows = v[u * n_items:(u + 1) * n_items].copy()
            if k in model.user_feats:
                rows = np.repeat(v[u:u + 1], n_items, axis=0)
            out[k] = rows
        return out

    reqs = {u: user_req(u) for u in range(4)}
    expect = {u: np.asarray(pred.predict(r)) for u, r in reqs.items()}

    # spy: how many rows the user tower traces over per dispatch
    seen = []
    orig_user_vector = type(model).user_vector

    def spy(self, params, inputs):
        u = jnp.concatenate([inputs.pooled[n] for n in self.user_feats], -1)
        seen.append(int(u.shape[0]))
        return orig_user_vector(self, params, inputs)

    server = ModelServer(pred, max_batch=64, max_wait_ms=20)
    try:
        # warm the single-request grouped bucket so the measured batch is
        # the only fresh trace
        server.request(reqs[0], group_users=True)
        type(model).user_vector = spy
        # submit all four <user, 8 items> requests back to back: the
        # batcher's coalescing window gathers them into ONE device batch
        replies = {u: server.submit(reqs[u], group_users=True)
                   for u in reqs}
        results = {u: r.get(timeout=30) for u, r in replies.items()}
        plain_out = server.request(reqs[0])  # plain lane, separate dispatch
    finally:
        type(model).user_vector = orig_user_vector
        server.close()

    versions = set()
    for u, out in results.items():
        assert not isinstance(out, Exception), out
        np.testing.assert_allclose(np.asarray(out[0]), expect[u], rtol=2e-5,
                                   atol=2e-5)
        versions.add(out[1])
    assert versions == {0}  # one shared snapshot stamped every request
    np.testing.assert_allclose(np.asarray(plain_out), expect[0],
                               rtol=2e-5, atol=2e-5)
    # the coalesced grouped batch ran a COMPRESSED user tower: its trace
    # saw at most one row per distinct user (<= 8 for a <=8-user batch),
    # never the 32 item rows the batch carried (spy records at trace
    # time — cache-hit dispatches are invisible, so the warm covers only
    # the single-request shape and the coalesced shape must trace here)
    stats = server.stats_snapshot()
    assert stats["requests"] == 6  # 1 warm + 4 grouped + 1 plain
    assert seen and min(seen) <= 8, seen


def test_multi_model_tfs_routes(tmp_path):
    """Multi-model serving over the TF-Serving REST shapes: two separately
    trained models behind one port, addressed by name; row-major
    'instances' bodies; model status; per-model reload."""
    import json
    import urllib.request
    import urllib.error

    from deeprec_tpu.serving import HttpServer

    dirs = {n: tmp_path / n for n in ("alpha", "beta")}
    trained = {n: make_trained(d, steps=3 if n == "alpha" else 6)
               for n, d in dirs.items()}
    servers = {
        n: ModelServer(Predictor(t[0], str(dirs[n])), max_batch=32,
                       max_wait_ms=1)
        for n, t in trained.items()
    }
    http = HttpServer(servers, port=0, default_model="alpha").start()
    base = f"http://127.0.0.1:{http.port}"

    def call(path, payload=None):
        req = urllib.request.Request(
            base + path,
            data=None if payload is None else json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
            method="GET" if payload is None else "POST",
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())

    try:
        assert call("/v1/models")["models"] == ["alpha", "beta"]
        # TFS model-status route reports each model's own version
        assert call("/v1/models/alpha")["model_version_status"][0]["version"] == "3"
        assert call("/v1/models/beta")["model_version_status"][0]["version"] == "6"

        batches = trained["alpha"][4]
        feats = {k: np.asarray(v)[:3].tolist()
                 for k, v in strip_labels(batches[0]).items()}
        # column-major per-model predict
        pa = call("/v1/models/alpha:predict", {"features": feats})["predictions"]
        pb = call("/v1/models/beta:predict", {"features": feats})["predictions"]
        assert len(pa) == len(pb) == 3
        assert np.abs(np.asarray(pa) - np.asarray(pb)).max() > 1e-6  # distinct models
        # bare route hits the default model
        pd = call("/v1/predict", {"features": feats})["predictions"]
        np.testing.assert_allclose(pd, pa, atol=1e-6)

        # TFS row-major instances body == column-major features body
        instances = [
            {k: feats[k][i] for k in feats} for i in range(3)
        ]
        pi = call("/v1/models/alpha:predict", {"instances": instances})["predictions"]
        np.testing.assert_allclose(pi, pa, atol=1e-6)

        # per-model reload: advance beta only; alpha's step is untouched
        model, tr, st, ck = trained["beta"][:4]
        for _ in range(2):
            st, _ = tr.train_step(st, trained["beta"][4][0])
        st, _ = ck.save_incremental(st)
        assert call("/v1/models/beta:reload", {})["updated"] is True
        assert call("/v1/models/beta")["model_version_status"][0]["version"] == "8"
        assert call("/v1/models/alpha")["model_version_status"][0]["version"] == "3"

        # unknown model -> 404 with the catalog
        try:
            call("/v1/models/nope:predict", {"features": feats})
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
            assert json.loads(e.read())["models"] == ["alpha", "beta"]
    finally:
        http.stop()
        for s in servers.values():
            s.close()


def test_protobuf_wire_end_to_end(tmp_path):
    """Reference wire format through both frontends: a serialized
    PredictRequest (predict.proto) in, a PredictResponse out, predictions
    byte-identical to the JSON path. Covers the C-ABI dispatch function
    (process_request) and the HTTP content-type route."""
    import urllib.request

    from deeprec_tpu.serving import HttpServer
    from deeprec_tpu.serving.cabi import process_proto, process_request
    from deeprec_tpu.serving.predict_pb import (
        ArrayProto,
        PredictRequest,
        PredictResponse,
    )

    model, tr, st, ck, batches, gen = make_trained(tmp_path)
    server = ModelServer(Predictor(model, str(tmp_path)), max_batch=64,
                         max_wait_ms=2)
    feats = {k: np.asarray(v)[:4] for k, v in strip_labels(batches[0]).items()}
    expect = np.asarray(server.predictor.predict(feats))

    wire = PredictRequest(
        signature_name="serving_default",
        inputs={k: ArrayProto.from_numpy(v) for k, v in feats.items()},
    ).serialize()

    # In-process (what the C ABI's process() forwards to)
    code, body = process_request(server, wire)
    assert code == 200
    out = PredictResponse.parse(body).outputs["probabilities"].to_numpy()
    np.testing.assert_allclose(out, expect, atol=1e-6)

    # output_filter: unknown alias -> client error, not a 500
    bad = PredictRequest(
        inputs={k: ArrayProto.from_numpy(v) for k, v in feats.items()},
        output_filter=["no_such_output"],
    ).serialize()
    code, body = process_proto(server, bad)
    assert code == 400 and b"no_such_output" in body

    # Garbage protobuf -> 400 plain-text, not a crash
    code, body = process_request(server, b"\xff\xfe\xfd")
    assert code == 400

    # HTTP with the protobuf content-type
    http = HttpServer(server, port=0).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{http.port}/v1/predict", data=wire,
            headers={"Content-Type": "application/x-protobuf"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.headers.get("Content-Type") == "application/x-protobuf"
            out2 = PredictResponse.parse(r.read())
        np.testing.assert_allclose(
            out2.outputs["probabilities"].to_numpy(), expect, atol=1e-6)
    finally:
        http.stop()
        server.close()


def test_sample_aware_compression_grouped_users(tmp_path):
    """Serving-side sample-aware compression (reference
    serving/processor/framework/graph_optimizer.cc): a <user, N items>
    batch routes the user tower through nn.apply_grouped — G distinct
    users' rows instead of B — with outputs row-for-row identical to the
    plain path."""
    import optax

    from deeprec_tpu.data import SyntheticTwoTower
    from deeprec_tpu.models import DSSM

    model = DSSM(emb_dim=8, capacity=1 << 12, num_user_feats=2,
                 num_item_feats=2, hidden=(32, 16))
    tr = Trainer(model, Adagrad(lr=0.1), optax.adam(2e-3))
    st = tr.init(0)
    gen = SyntheticTwoTower(batch_size=128, num_user=2, num_item=2,
                            vocab=500, seed=17)
    for _ in range(3):
        st, _ = tr.train_step(st, J(gen.batch()))
    ck = CheckpointManager(str(tmp_path), tr)
    ck.save(st)

    pred = Predictor(model, str(tmp_path))

    # <user, N items>: 4 distinct users x 8 candidate items each
    base = {k: np.asarray(v) for k, v in gen.batch().items()
            if not k.startswith("label")}
    B, n_users, n_items = 32, 4, 8
    batch = {}
    for k, v in base.items():
        rows = v[:B].copy()
        if k in model.user_feats:  # repeat each user's features x8
            rows = np.repeat(v[:n_users], n_items, axis=0)
        batch[k] = rows

    # count the rows the user tower actually traces over
    seen = []
    orig_user_vector = type(model).user_vector

    def spy(self, params, inputs):
        u = jnp.concatenate(
            [inputs.pooled[n] for n in self.user_feats], -1)
        seen.append(int(u.shape[0]))
        return orig_user_vector(self, params, inputs)

    type(model).user_vector = spy
    try:
        plain = np.asarray(pred.predict(batch))
        grouped = np.asarray(pred.predict(batch, group_users=True))
    finally:
        type(model).user_vector = orig_user_vector

    np.testing.assert_allclose(grouped, plain, rtol=2e-6, atol=2e-6)
    # plain path traced the full batch; grouped path traced 4 users
    assert max(seen) == B
    assert min(seen) == n_users  # fewer user-tower FLOPs: 4 rows, not 32

    # the HTTP frontend routes the flag end-to-end (and a tower-less
    # model would get a 400 through the same route)
    import json as _json
    import urllib.request

    from deeprec_tpu.serving import HttpServer, ModelServer

    server = ModelServer(pred, max_batch=64, max_wait_ms=1)
    http = HttpServer(server, port=0).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{http.port}/v1/predict",
            data=_json.dumps({
                "features": {k: v.tolist() for k, v in batch.items()},
                "group_users": True,
            }).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            via_http = _json.loads(r.read())["predictions"]
        np.testing.assert_allclose(np.asarray(via_http), plain,
                                   rtol=2e-5, atol=2e-5)
    finally:
        http.stop()
        server.close()

    # odd client batch sizes ride the power-of-two bucket ladder (no
    # per-size compile storm) and slice back to the client row count
    odd = {k: v[:29] for k, v in batch.items()}
    out_odd = np.asarray(pred.predict(odd, group_users=True))
    assert out_odd.shape[0] == 29
    np.testing.assert_allclose(out_odd, plain[:29], rtol=2e-6, atol=2e-6)

    # a model without a tower split fails loudly, not silently wrong
    pred.model = WDL(emb_dim=8, capacity=1 << 12, hidden=(32,),
                     num_cat=4, num_dense=2)
    try:
        pred.predict({}, group_users=True)
        raise AssertionError("expected ValueError")
    except ValueError as e:
        assert "tower" in str(e)


def test_whitespace_prefixed_json_dispatch(tmp_path):
    """Whitespace-prefixed JSON must route to the JSON path even when the
    bytes happen to proto3-parse as a PredictRequest with no inputs
    (unknown fields are skipped, so 'parse succeeded' alone proves
    nothing — the dispatch requires actual inputs before taking the
    protobuf path)."""
    import json

    from deeprec_tpu.serving.cabi import process_request
    from deeprec_tpu.serving.predict_pb import PredictRequest

    model, tr, st, ck, batches, gen = make_trained(tmp_path)
    server = ModelServer(Predictor(model, str(tmp_path)), max_batch=64,
                         max_wait_ms=2)
    try:
        feats = {
            k: np.asarray(v)[:2].tolist()
            for k, v in strip_labels(batches[0]).items()
        }
        body = {"features": feats}

        for prefix in (b" ", b"\n", b"\t", b"\r\n", b"   "):
            payload = prefix + json.dumps(body).encode()
            code, out = process_request(server, payload)
            assert code == 200, (prefix, out)
            assert b"predictions" in out

        # Adversarial: pad the JSON until the bytes ALSO parse as a
        # proto3 PredictRequest with empty inputs — the exact case a
        # parse-failure-only fallback misses.
        crafted = None
        for pad in range(0, 512):
            payload = b" " + json.dumps(
                {"_pad": "x" * pad, "features": feats}
            ).encode()
            try:
                if not PredictRequest.parse(payload).inputs:
                    crafted = payload
                    break
            except Exception:
                continue
        if crafted is not None:
            code, out = process_request(server, crafted)
            assert code == 200 and b"predictions" in out, out
    finally:
        server.close()


def test_server_group_replicas_concurrent_and_rolling_update(tmp_path):
    """SessionGroup parity (direct_session_group.h:28): N replicas on N
    devices serve concurrently behind one request front and one
    checkpoint watcher; an update rolls across every replica."""
    import jax

    from deeprec_tpu.serving import ServerGroup

    model, tr, st, ck, batches, gen = make_trained(tmp_path)
    req = strip_labels(batches[0])
    expect = np.asarray(Predictor(model, str(tmp_path)).predict(req))

    assert len(jax.local_devices()) >= 2  # conftest forces 8 CPU devices
    group = ServerGroup(model, str(tmp_path), replicas=2, max_wait_ms=1.0)
    try:
        # replicas live on distinct devices
        devs = {
            next(iter(jax.tree.leaves(s.predictor._state))).devices().pop()
            for s in group.members
        }
        assert len(devs) == 2
        assert group.predictor.model_info()["replicas"] == 2

        # concurrent clients: all answers correct, both replicas exercised
        errs = []
        outs = [None] * 12

        def client(i):
            try:
                sl = {k: v[i * 8 : i * 8 + 8] for k, v in req.items()}
                outs[i] = np.asarray(group.request(sl))
            except Exception as e:  # surfaced below
                errs.append(e)

        threads = [threading.Thread(target=client, args=(i,)) for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errs, errs
        got = np.concatenate(outs[: 96 // 8])
        np.testing.assert_allclose(got, expect[:96], rtol=2e-5, atol=2e-5)

        # train on, save a newer checkpoint, poll once -> EVERY replica
        st2 = st
        for b in batches:
            st2, _ = tr.train_step(st2, b)
        ck.save(st2)
        assert group.predictor.poll_updates() is True
        steps = {s.predictor.step for s in group.members}
        assert steps == {int(st2.step)}, steps
    finally:
        group.close()

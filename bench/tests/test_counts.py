"""The FLOP and byte counts, on shapes counted by hand."""
import numpy as np

from bench import common, counts, serving, traffic
from bench.run import model_config

TINY = {"d_model": 8, "n_heads": 2, "n_kv_heads": 1, "d_ff": 16, "vocab_size": 10,
        "n_layers": 2, "act": "swiglu"}


def test_matmul_params():
    # attention 8*(2+2)*4 + 2*4*8 = 192, SwiGLU 3*8*16 = 384, per layer;
    # two layers plus the 8 x 10 output head
    assert counts.matmul_params(TINY) == 2 * (192 + 384) + 80


def test_decode_step_flops():
    # slot 0 decodes at position 5 (6 keys); slot 1 prefills positions
    # 0..2 (1 + 2 + 3 keys); 4*2*4 = 32 FLOPs per key per layer
    grants = [(0, 5, 1), (1, 0, 3)]
    assert counts.decode_step_flops(TINY, grants) == 2 * 1232 * 4 + 2 * 32 * 12


def test_paged_attention_work():
    flops, byts = counts.paged_attention_work(TINY, [(0, 5, 1), (1, 0, 3)])
    assert flops == 32 * 12
    # live K and V (6 and 3 rows of 1 head x 4 x 2 bytes), queries and outputs
    assert byts == (2 * 6 * 4 * 2 + 2 * 1 * 2 * 4 * 2) + (2 * 3 * 4 * 2 + 2 * 3 * 2 * 4 * 2)


def test_train_step_flops():
    bidir = dict(TINY, n_kv_heads=2)
    p = counts.matmul_params(bidir)
    assert counts.train_step_flops(bidir, tokens=10, seq_len=5) == 3 * (2 * p + 2 * 32 * 5) * 10


def test_roofline_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_s(1000, 50, peaks) == (10.0, "compute")
    assert counts.roofline_s(100, 500, peaks) == (50.0, "memory")


def _grants_with_pool(num_pages):
    from repro.serve import Request

    _, conf, mix = common.cell("internlm2_1_8b-chat")
    mix = {**mix, **mix["rehearsal"]}
    mix["engine"] = {**mix["engine"], "num_pages": num_pages}
    conf = {**conf, "model": {**conf["model"], **conf["rehearsal"]["model"]}}
    cfg = model_config(conf)
    eng, _ = serving.build_engine(cfg, conf, mix, seed=3)
    steps = []
    serving.add_seams(eng, common.Spans(False), steps)
    for q in traffic.requests(mix, 3, 2.0, cfg.vocab_size):
        eng.submit(Request(uid=q.uid, prompt=q.prompt, max_new_tokens=q.max_new))
    eng.run()
    return conf["model"], [s["grants"] for s in steps]


def test_paged_work_ignores_the_pool_size():
    """At fixed live tokens a larger pool changes nothing in the count:
    the same requests, served with 64 and with 512 pages, count the same
    FLOPs and bytes step by step."""
    model, small = _grants_with_pool(64)
    _, large = _grants_with_pool(512)
    work = lambda gs: [counts.paged_attention_work(model, g) for g in gs]  # noqa: E731
    assert small == large
    assert work(small) == work(large)
    assert np.sum([b for _, b in work(small)]) > 0

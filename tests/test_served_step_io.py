"""The served step crosses the host boundary once each way (PR 38): the
decode chunk and the mixed step split the key themselves, take temperature
and top-p as device scalars made once, read every host input from one int32
buffer and hand every host-visible output back in one int32 vector. None of
that changes a result: each engine mode serves the tokens, the
log-probabilities (bit for bit) and the routed layers' counts the engine at
the parent commit (6111808) served for the same seeds, sizes and prompts,
written down from a run of that tree."""
import dataclasses

import numpy as np
import pytest

import paddle_tpu as paddle

SAMPLED = dict(do_sample=True, temperature=0.7, top_p=0.9, seed=1234)

CASES = {
    "unified-greedy": ("llama", dict()),
    "unified-sampled": ("llama", SAMPLED),
    "unified-sampled-logprobs": ("llama", dict(SAMPLED, logprobs=True)),
    "split-greedy": ("llama", dict(unified_step=False)),
    "split-sampled": ("llama", dict(SAMPLED, unified_step=False)),
    "pipelined-greedy": ("llama", dict(double_buffer=True)),
    "pipelined-sampled": ("llama", dict(SAMPLED, double_buffer=True)),
    "routed-logprobs": ("mellum", dict(logprobs=True)),
    "routed-sampled-logprobs": ("mellum", dict(SAMPLED, logprobs=True)),
}


def _engine(model, kw):
    from paddle_tpu.serving import ContinuousBatchingEngine

    if model == "mellum":
        from paddle_tpu.models import MellumConfig, mellum

        cfg = MellumConfig.tiny()
        p = mellum.init_serving_params(cfg, seed=7, dtype="float32")
        return cfg, ContinuousBatchingEngine(
            cfg, p, slots=2, prompt_bucket=16, block_size=8,
            max_prompt_len=64, max_new_tokens=12, token_budget=16,
            steps_per_sync=4, dtype="float32", **kw)
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = dataclasses.replace(LlamaConfig.tiny(), num_key_value_heads=2)
    paddle.seed(21)
    p = dict(LlamaForCausalLM(cfg).raw_state())
    return cfg, ContinuousBatchingEngine(
        cfg, p, slots=2, prompt_bucket=8, max_prompt_len=24,
        max_new_tokens=9, block_size=8, steps_per_sync=2, **kw)


def serve(case):
    """{"tokens", "logprob_bits", "moe"} of `case` served to the end: four
    requests on two slots, so prompts queue, prefill in windows beside live
    decode rows and retire mid-chunk."""
    model, kw = CASES[case]
    cfg, eng = _engine(model, kw)
    rng = np.random.default_rng(3)
    lengths = (5, 19, 3, 12) if model == "llama" else (21, 40, 6, 17)
    reqs = [eng.add_request(rng.integers(1, cfg.vocab_size, n).tolist(),
                            max_new=m)
            for n, m in zip(lengths, (9, 6, 8, 7))]
    eng.run(max_iters=500)
    assert all(r.done and not r.failed for r in reqs)
    return {"tokens": [list(r.tokens) for r in reqs],
            "logprob_bits": [np.asarray(r.logprobs, np.float32)
                             .view(np.int32).tolist() for r in reqs],
            "moe": {k: v.tolist() for k, v in sorted(eng.moe_counts.items())}
            if eng._routed else {}}


BEFORE = {'pipelined-greedy': {'logprob_bits': [[], [], [], []],
                      'moe': {},
                      'tokens': [[54, 15, 49, 41, 35, 40, 29, 80, 40],
                                 [7, 102, 3, 68, 12, 35],
                                 [7, 7, 6, 78, 124, 33, 7, 113],
                                 [69, 23, 83, 58, 86, 123, 78]]},
 'pipelined-sampled': {'logprob_bits': [[], [], [], []],
                       'moe': {},
                       'tokens': [[41, 96, 34, 109, 34, 32, 61, 112, 124],
                                  [106, 123, 75, 17, 12, 116],
                                  [7, 93, 13, 127, 7, 99, 6, 85],
                                  [25, 12, 75, 33, 41, 49, 117]]},
 'routed-logprobs': {'logprob_bits': [[-1068228832, -1067606154, -1067546248,
                                       -1069047808, -1070042830, -1071066851,
                                       -1068722051, -1069929158, -1071511525],
                                      [-1069343121, -1068775243, -1068818980,
                                       -1068112231, -1068732998, -1069249086],
                                      [-1068697728, -1067915609, -1069473741,
                                       -1070909569, -1070989833, -1071392859,
                                       -1071126576, -1070654287],
                                      [-1069831764, -1069019127, -1068670257,
                                       -1069509677, -1069131140, -1069012464,
                                       -1067597770]],
                     'moe': {'chunk': [32, 1024, 182, 403, 4096],
                             'decode': [176, 704, 508, 302, 22528]},
                     'tokens': [[93, 46, 116, 13, 15, 15, 46, 1, 105],
                                [56, 123, 89, 6, 123, 6],
                                [66, 127, 68, 68, 68, 68, 68, 68],
                                [8, 53, 53, 60, 57, 10, 53]]},
 'routed-sampled-logprobs': {'logprob_bits': [[-1068228832, -1065705500,
                                               -1063980483, -1065902891,
                                               -1063358501, -1065668898,
                                               -1063327641, -1065707133,
                                               -1064683946],
                                              [-1063935004, -1063643186,
                                               -1066213237, -1065479198,
                                               -1065256948, -1063246132],
                                              [-1065032014, -1065094358,
                                               -1066586960, -1064375521,
                                               -1063394631, -1066804774,
                                               -1065849498, -1064120034],
                                              [-1066155153, -1063691870,
                                               -1065616236, -1069310846,
                                               -1070151457, -1064758926,
                                               -1068709042]],
                             'moe': {'chunk': [32, 1024, 182, 403, 4096],
                                     'decode': [176, 704, 493, 315, 22528]},
                             'tokens': [[93, 84, 34, 46, 14, 0, 87, 81, 28],
                                        [33, 77, 24, 16, 40, 73],
                                        [4, 107, 6, 115, 56, 86, 85, 9],
                                        [40, 99, 38, 53, 54, 85, 54]]},
 'split-greedy': {'logprob_bits': [[], [], [], []],
                  'moe': {},
                  'tokens': [[54, 15, 49, 41, 35, 40, 29, 80, 40],
                             [7, 102, 3, 68, 12, 35],
                             [7, 7, 6, 78, 124, 33, 7, 113],
                             [69, 23, 83, 58, 86, 123, 78]]},
 'split-sampled': {'logprob_bits': [[], [], [], []],
                   'moe': {},
                   'tokens': [[54, 100, 112, 56, 41, 112, 37, 117, 97],
                              [7, 42, 66, 20, 87, 42],
                              [73, 68, 104, 49, 24, 7, 78, 13],
                              [27, 31, 33, 86, 116, 64, 116]]},
 'unified-greedy': {'logprob_bits': [[], [], [], []],
                    'moe': {},
                    'tokens': [[54, 15, 49, 41, 35, 40, 29, 80, 40],
                               [7, 102, 3, 68, 12, 35],
                               [7, 7, 6, 78, 124, 33, 7, 113],
                               [69, 23, 83, 58, 86, 123, 78]]},
 'unified-sampled': {'logprob_bits': [[], [], [], []],
                     'moe': {},
                     'tokens': [[41, 96, 34, 109, 34, 32, 61, 112, 124],
                                [106, 123, 75, 67, 68, 63],
                                [7, 73, 68, 93, 13, 127, 90, 99],
                                [25, 12, 75, 33, 41, 49, 117]]},
 'unified-sampled-logprobs': {'logprob_bits': [[-1067231498, -1063116671,
                                                -1067476784, -1068172928,
                                                -1067208947, -1066837228,
                                                -1065833636, -1063578570,
                                                -1063679401],
                                               [-1065587022, -1065254362,
                                                -1063987710, -1068972586,
                                                -1065544784, -1065045285],
                                               [-1069723401, -1067576782,
                                                -1063850210, -1063312786,
                                                -1067474944, -1069837982,
                                                -1064753769, -1064873916],
                                               [-1064914532, -1064985567,
                                                -1067241936, -1064976679,
                                                -1065089774, -1067984752,
                                                -1069260578]],
                              'moe': {},
                              'tokens': [[41, 96, 34, 109, 34, 32, 61, 112,
                                          124],
                                         [106, 123, 75, 67, 68, 63],
                                         [7, 73, 68, 93, 13, 127, 90, 99],
                                         [25, 12, 75, 33, 41, 49, 117]]}}


@pytest.mark.parametrize("case", sorted(CASES))
def test_served_results_are_the_parents(case):
    assert serve(case) == BEFORE[case]

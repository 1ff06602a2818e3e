"""Smoke sizes of each driver's configuration and traffic, for CPU runs."""


def taskset(cfg, mix):
    return (dict(cfg, processors=8, task_matrix=128),
            dict(mix, rounds=2, check_sample=8))


def serve(cfg, mix):
    cfg = dict(cfg, hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=2, vocab_size=509,
               engine={"lanes": 4, "max_len": 96},
               # logits at this width are about 7x smaller than at the
               # published one; sound runs read ~0.003, the float8
               # control ~0.08 (CPU)
               limits={"max_logit_gap": 0.03})
    mix = dict(mix, arrivals=dict(mix["arrivals"], rate=20.0),
               prompt=dict(mix["prompt"], median=24, min=8, max=64,
                           round_to=16),
               output=dict(mix["output"], median=8, min=4, max=16),
               check_sample=4)
    return cfg, mix


def shrink(cfg, mix):
    return globals()[cfg["driver"]](cfg, mix)

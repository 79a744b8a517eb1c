"""A BROKEN program for the tests: JoyAI-LLM-Flash trained without its MTP
term (the module runs, its loss weighs nothing). The cell must read it as
not correct."""
from benchmark.programs import paddle_joyai
from benchmark.programs.paddle_joyai import enable_compile_cache  # noqa: F401


def build_trainer(cfg, traffic, make_weights, devices):
    return paddle_joyai.build_trainer(dict(cfg, mtp_loss_weight=0.0),
                                      traffic, make_weights, devices)

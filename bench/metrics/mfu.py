"""Megatick programs, whole step: model FLOPs of the tokens the window
served (prompt and output tokens once each, from their positions),
over window x chips x the chip's bf16 peak."""


def read(run):
    return run.mfu_percent()

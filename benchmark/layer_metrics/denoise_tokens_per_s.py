"""Masked positions denoised a second of the window: under the
block-diffusion objective the program's ``trainer.tokens`` counter counts
the positions the loss scores, the masked ones -- about a quarter of the
positions the layers compute, both streams counted -- so this is
``train_tokens_per_s``' reading of it, under the name a diffusion job
reports."""
from benchmark.layer_metrics.train_tokens_per_s import read  # noqa: F401

"""Bytes the cache holds per token per attention layer (B) under
compressed-latent attention: the stat ``latent_cache_bytes_per_token`` reads
(``kv_bytes_per_token_layer``, from the leaves the engine allocated), under the
name of what it should read here. K and V of 2 kv heads of 128 joined in one
leaf in bf16 read 1,024; 4,096 would be the leaf padded to a whole (16, 128)
tile a token. A program without the stat: ``None``."""
from perfbench.run import load_reader

read = load_reader("latent_cache_bytes_per_token")

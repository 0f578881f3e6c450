"""Plain float32 reference of the benchmark's ``attn_moe`` decoders."""

"""Plain float32 references, written from the published equations.
Nothing here imports ray_tpu.models: `correct` must not rest on the
code it judges."""

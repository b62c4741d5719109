"""Model multiplexing: many models behind one deployment (the
LoRA-serving pattern): replicas load models by id into a bounded LRU
and the router keeps each model's requests on the replica that already
holds it.

Run: python examples/08_model_multiplexing.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax

import ray_tpu
from ray_tpu import serve

ray_tpu.init()


@serve.deployment(num_replicas=2, max_ongoing_requests=8)
class AdapterServer:
    @serve.multiplexed(max_num_models_per_replica=2)
    def get_model(self, model_id: str):
        print(f"[replica {os.getpid()}] loading {model_id}")
        # stand-in for loading a LoRA adapter / fine-tune by id
        return {"id": model_id, "scale": len(model_id)}

    def __call__(self, prompt: str):
        model = self.get_model(serve.get_multiplexed_model_id())
        return f"{model['id']}({model['scale']}): {prompt[::-1]}"


handle = serve.run(AdapterServer.bind())
for model_id in ("alpha", "beta", "alpha", "gamma", "alpha"):
    out = ray_tpu.get(
        handle.options(multiplexed_model_id=model_id).remote("hello"))
    print(model_id, "->", out)
serve.shutdown()
ray_tpu.shutdown()

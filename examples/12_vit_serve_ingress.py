"""Train a ViT classifier, then serve it behind @serve.ingress HTTP
routes (path templates + verbs on a deployment class).

Run:
  JAX_PLATFORMS=cpu python examples/12_vit_serve_ingress.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))      # repo root (run from anywhere)

import jax

import json
import urllib.request

import jax.numpy as jnp
import numpy as np
import optax

import ray_tpu
from ray_tpu import serve
from ray_tpu.models import ViT, classification_loss, vit_tiny
from ray_tpu.serve.http_proxy import start_http, stop_http

# ---- train a tiny ViT on synthetic data ---------------------------------
cfg = vit_tiny()
model = ViT(cfg)
rng = np.random.RandomState(0)
imgs = jnp.asarray(rng.rand(32, 32, 32, 3), jnp.float32)
labels = jnp.asarray(rng.randint(0, cfg.num_classes, 32))
params = model.init(jax.random.PRNGKey(0), imgs[:1])
opt = optax.adam(1e-2)
opt_state = opt.init(params)


@jax.jit
def step(params, opt_state):
    loss, g = jax.value_and_grad(
        lambda p: classification_loss(model.apply(p, imgs),
                                      labels))(params)
    upd, opt_state = opt.update(g, opt_state, params)
    return optax.apply_updates(params, upd), opt_state, loss


for i in range(10):
    params, opt_state, loss = step(params, opt_state)
print(f"trained 10 steps, final loss {float(loss):.3f}")
host_params = jax.device_get(params)

# ---- serve it behind HTTP routes ----------------------------------------
ray_tpu.init()


@serve.deployment
@serve.ingress
class Classifier:
    def __init__(self, params):
        self.model = ViT(vit_tiny())
        self.params = params
        self._predict = jax.jit(
            lambda p, x: self.model.apply(p, x).argmax(-1))

    @serve.route("/healthz")
    def health(self, payload):
        return {"status": "ok"}

    @serve.route("/classify", methods=["POST"])
    def classify(self, payload):
        x = jnp.asarray(payload["image"], jnp.float32)[None]
        return {"label": int(self._predict(self.params, x)[0])}

    @serve.route("/classify/{label}", methods=["POST"])
    def check(self, payload, label):
        x = jnp.asarray(payload["image"], jnp.float32)[None]
        pred = int(self._predict(self.params, x)[0])
        return {"predicted": pred, "match": pred == int(label)}


serve.run(Classifier.bind(host_params))
proxy = start_http(port=0)
base = f"http://127.0.0.1:{proxy.port}/Classifier"
try:
    with urllib.request.urlopen(f"{base}/healthz", timeout=30) as r:
        print("healthz:", json.loads(r.read()))
    img = np.asarray(imgs[0]).tolist()
    req = urllib.request.Request(
        f"{base}/classify", method="POST",
        data=json.dumps({"image": img}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        out = json.loads(r.read())
    print("classify:", out)
    req = urllib.request.Request(
        f"{base}/classify/{out['result']['label']}", method="POST",
        data=json.dumps({"image": img}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        print("check:", json.loads(r.read()))
finally:
    stop_http()
    serve.shutdown()
    ray_tpu.shutdown()

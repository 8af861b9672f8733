"""Model kind ``mlp``: the paper's dense classifier split after a layer.

A configuration's ``model`` names its kind (``"kind"``, default ``mlp``);
the harness loads ``perfbench/models/<kind>.py`` by that name and takes
everything model-specific from it:

* ``program_model(model)``: the model argument the program's entry takes;
  the only function of a kind that imports the program;
* ``make_data(data, M, samples_per_client, seed)``: ``(clients, test)``,
  clients ``{"x": (M, n, ...), "y": (M, n)}`` and test ``(x, y)``, all
  from the seed;
* ``init_params(framework, model, key)``: the reference's trainable
  parameters, the client model c(.) under SplitMe (the inverse model
  s^-1(.) is the reference's own MLP) and the whole model under FedAvg;
* ``client_forward(params, x, dt)``: c(x), the smashed data, and
  ``full_forward(params, x, dt)``: the whole model's logits; ``dt``
  narrows the matmul inputs (float32 accumulation), None leaves them;
* ``sizes(model)``: ``split_width`` (values per sample at the cut),
  ``n_classes``, ``client_params``, ``inverse_params`` and
  ``full_params`` (weights and biases), and ``server_dims`` (the server's
  layer widths, from the cut to the classes: the inverse model and Step 4
  run over them);
* ``forward_flops(model, part)`` and ``backward_flops(model, part)``:
  one sample's operations through ``client`` or ``full``;
* optionally ``client_block`` and ``sample_block``, for a model too large
  to copy per client: how many clients a reference round trains at once
  (then only A_t's, never the whole fleet), and how many samples a
  client forward of the reference takes at once.  Left out, a round
  trains all M clients at once and a forward takes all its samples.

Here: an MLP ``n_features -> hidden... -> n_classes`` with ReLU between
layers, split after ``split_index`` layers; the client keeps a ReLU after
its last layer.  It is small enough for the defaults and sets neither
block size.
"""
from __future__ import annotations

import flops
import oran_data
import reference


def dims(model: dict) -> tuple:
    return (model["n_features"], *model["hidden"], model["n_classes"])


def _part(model: dict, part: str) -> tuple:
    d = dims(model)
    if part == "client":
        return d[:model["split_index"] + 1]
    if part == "full":
        return d
    raise KeyError(f"the mlp kind has no part {part!r}")


def param_count(d) -> int:
    return sum(d[i] * d[i + 1] + d[i + 1] for i in range(len(d) - 1))


def program_model(model: dict):
    from repro.configs.splitme_dnn import DNNConfig
    return DNNConfig(n_features=model["n_features"],
                     n_classes=model["n_classes"],
                     hidden=tuple(model["hidden"]),
                     split_index=model["split_index"],
                     activation=model["activation"])


def make_data(data: dict, M: int, samples_per_client: int, seed: int):
    return oran_data.make(data, M, samples_per_client, seed)


def init_params(framework: str, model: dict, key):
    part = "client" if framework == "splitme" else "full"
    return reference.init_mlp(key, _part(model, part))


def client_forward(params, x, dt=None):
    return reference.mlp(params, x, True, dt)


def full_forward(params, x, dt=None):
    return reference.mlp(params, x, False, dt)


def sizes(model: dict) -> dict:
    d = dims(model)
    server = d[model["split_index"]:]
    return {"split_width": server[0], "n_classes": d[-1],
            "client_params": param_count(_part(model, "client")),
            "inverse_params": param_count(tuple(reversed(server))),
            "full_params": param_count(d), "server_dims": server}


def forward_flops(model: dict, part: str) -> int:
    return flops.forward(_part(model, part))


def backward_flops(model: dict, part: str) -> int:
    return flops.backward(_part(model, part))

"""The harness's replica: register the configuration file's model, then
hand over to the product's own CLI (``aigw_tpu.cli.main``).

    python cellbench/serve_child.py <config.json> tpuserve --model <name> ...

The file holds the published ``config.json`` keys at its top level. Its
``cellbench.fields`` maps each field of the family's configuration
dataclass onto the published key it takes its value from, and
``cellbench.model_fields`` gives the fields no published key covers
(``capacity_factor``, ``attn_bias``), so a new family is a new file and
nothing here. Nothing else of the program is touched: the engine, the
scheduler and the server are the ones ``python -m aigw_tpu tpuserve``
runs.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def model_kwargs(doc: dict) -> dict:
    """Arguments of the family's configuration dataclass."""
    cb = doc["cellbench"]
    kwargs = {field: doc[key] for field, key in cb["fields"].items()}
    kwargs.update(cb.get("model_fields", {}))
    return kwargs


def config_class(family: str) -> type:
    """The family's configuration dataclass, as the program's own
    registry has it on a model of that family."""
    from aigw_tpu.models import registry

    return next(type(spec.config) for spec in registry._REGISTRY.values()
                if spec.family == family)


def register(doc: dict) -> None:
    from aigw_tpu.models.registry import ModelSpec, register_model

    cb = doc["cellbench"]
    register_model(ModelSpec(
        cb["name"], cb["family"],
        config_class(cb["family"])(**model_kwargs(doc)),
        chat_template=cb["chat_template"]))


def main(argv: list[str]) -> int:
    with open(argv[0]) as f:
        register(json.load(f))
    from aigw_tpu.cli import main as cli_main

    return cli_main(argv[1:]) or 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

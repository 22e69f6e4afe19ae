"""The shape of the token side (ISSUE 44): a token family is its own file and
ONE line of ``models/families.py`` ``TOKEN_STACKS``. The arrows of the import
graph point one way (kernels <- shared token layers <- the families' files <-
the registry <- model, factory, contracts; the engine and the trainer reach the
shared layers alone), read off the sources with ``ast``; and everything the
program knows of a family follows from its registry line. No program is run:
imports, sets and sources."""

import ast
import glob
import inspect
import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from hydragnn_tpu.analysis import contracts  # noqa: E402
from hydragnn_tpu.models import base, convs, create, families  # noqa: E402
from hydragnn_tpu.models.create import create_model_config  # noqa: E402

PACKAGE = "hydragnn_tpu"
MODELS = PACKAGE + ".models."
# The shared token layers: what every family's file is built from.
SHARED = {MODELS + name for name in ("token_attention", "token_common", "token_routed")}
REGISTRY = MODELS + "families"
# The families' own modules, as the registry has them (never listed here).
FAMILY_MODULES = {
    part.__module__ for stack in families.TOKEN_STACKS.values() for part in stack
}


def _imports(path):
    """{(absolute module, name or None)} of every import statement in the
    file, relative ones resolved against the file's package; ``from pkg import
    name`` also counts as the module ``pkg.name`` (it may be one)."""
    rel = os.path.relpath(path, REPO)[: -len(".py")].split(os.sep)
    package = rel[:-1]
    found = set()
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found.update((alias.name, None) for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base_parts = package[: len(package) - node.level + 1] if node.level else []
            module = ".".join(base_parts + (node.module.split(".") if node.module else []))
            for alias in node.names:
                found.add((module, alias.name))
                found.add((module + "." + alias.name, None))
    return found


def _program_files(*subdirs):
    return sorted(
        path for sub in subdirs
        for path in glob.glob(os.path.join(REPO, PACKAGE, sub, "**", "*.py"), recursive=True)
    )


def _module_of(path):
    return os.path.relpath(path, REPO)[: -len(".py")].replace(os.sep, ".")


def _family_imports(path):
    return sorted({m for m, _ in _imports(path) if m in FAMILY_MODULES})


def _no_family_imports_another():
    assert len(FAMILY_MODULES) == len(families.TOKEN_STACKS)  # a file a family
    for path in _program_files("models"):
        if _module_of(path) in FAMILY_MODULES:
            others = [m for m in _family_imports(path) if m != _module_of(path)]
            assert not others, f"{_module_of(path)} imports {others}"


def _shared_layers_import_no_family():
    for module in sorted(SHARED):
        path = os.path.join(REPO, *module.split(".")) + ".py"
        assert os.path.exists(path), f"no shared token module {module}"
        reached = {m for m, _ in _imports(path)}
        assert not reached & (FAMILY_MODULES | {REGISTRY}), (module, reached)
    # ... and a family's file is built from them.
    for path in _program_files("models"):
        if _module_of(path) in FAMILY_MODULES:
            assert {m for m, _ in _imports(path)} & SHARED, _module_of(path)


def _only_the_registry_imports_a_family():
    importers = {
        _module_of(path): _family_imports(path)
        for path in _program_files("")
        if _module_of(path) not in FAMILY_MODULES and _family_imports(path)
    }
    assert set(importers) == {REGISTRY}, importers
    assert sorted(importers[REGISTRY]) == sorted(FAMILY_MODULES)


def _serve_and_train_import_no_private_model_name():
    for path in _program_files("serve", "train"):
        private = sorted(
            (m, name) for m, name in _imports(path)
            if m.startswith(MODELS) and name and name.startswith("_")
        )
        assert not private, f"{_module_of(path)} imports {private}"


@pytest.mark.parametrize("rule", [
    _no_family_imports_another, _shared_layers_import_no_family,
    _only_the_registry_imports_a_family, _serve_and_train_import_no_private_model_name,
], ids=lambda rule: rule.__name__.strip("_"))
def pytest_the_token_sides_imports_point_one_way(rule):
    rule()


PINNED = ("lfm2", "laguna", "mistral4", "mellum")


def _published_arch(family):
    """The family's ``Architecture`` block as the benchmark's configuration
    publishes it, with what config completion adds."""
    for path in sorted(glob.glob(os.path.join(REPO, "graftbench", "configs", "*.json"))):
        with open(path) as f:
            arch = json.load(f)["NeuralNetwork"]["Architecture"]
        if arch["model_type"] == family:
            v = arch["vocab_size"]
            return dict(
                arch, input_dim=1, output_dim=[v], output_type=["node"],
                token_minmax=[0.0, v - 1.0], head_loss=["cross_entropy"],
                class_minmax=[[0.0, v - 1.0]],
            )
    raise AssertionError(f"no configuration under graftbench/configs builds {family}")


def _without(source, *patterns):
    for pattern in patterns:
        source = re.sub(pattern, "", source, flags=re.S)
    return source


@pytest.mark.parametrize("family", sorted(families.TOKEN_STACKS))
def pytest_a_token_family_is_one_registry_line(family):
    sizes, block = families.TOKEN_STACKS[family]
    # Every derived set holds it.
    assert family in families.TOKEN_FAMILIES
    assert family in families.CONV_TYPES
    assert family in families.SORTED_PATH_FAMILIES
    assert family in families.POSITION_FAMILIES
    assert base.CONV_TYPES is families.CONV_TYPES and not hasattr(convs, "TOKEN_STACKS")
    # Its sizes class and its block live in ONE module, the family's own.
    assert sizes.__module__ == block.__module__ != REGISTRY
    # The factory builds it from its Architecture block through the one
    # argument, and the model holds the sizes in the one field.
    arch = _published_arch(family)
    model = create_model_config(arch)
    assert model.conv_type == family and type(model.token_cfg) is sizes
    assert model.token_cfg == sizes.from_arch(arch, arch["num_conv_layers"])
    # It counts routing where some layer of it routes (PR 45's stack routes in none).
    assert model.needs_positions and model.counts_routing == any(
        model.token_cfg.routed(i) for i in range(model.num_conv_layers)
    )
    assert "token_arch" in inspect.signature(create.create_model).parameters
    assert "token_cfg" in type(model).__dataclass_fields__
    lower = family.lower()
    for other in families.TOKEN_STACKS:
        assert other.lower() not in inspect.signature(create.create_model).parameters
        assert other.lower() not in type(model).__dataclass_fields__
        # The benchmark's pinned names (ROADMAP D25): read-only, this family's
        # alone; the four that were there, and none for a family added since
        # (its benchmark file reads ``model.token_cfg``).
        if other.lower() in PINNED:
            assert getattr(model, other.lower()) is (model.token_cfg if other == family else None)
        else:
            assert not hasattr(type(model), other.lower())
    with pytest.raises(ValueError, match="token_arch"):
        create.create_model(
            family, 1, 8, (4,), ("node",), arch["output_heads"], [1.0], 1
        )
    # The program's sources spell the family nowhere but in the registry and
    # in ``HydraGNN``'s four pinned properties.
    pinned = rf'    @property\n    def {lower}\(self\):\n        return self\.token_cfg if self\.conv_type == "{family}" else None\n'
    for module, allowed in (
        (create, ()), (convs, ()), (contracts, ()), (base, (pinned,) if lower in PINNED else ()),
    ):
        source = _without(inspect.getsource(module), *allowed)
        assert not re.search(lower, source, flags=re.I), (
            f"{module.__name__} spells {family}"
        )
    assert inspect.getsource(families).count(f'"{family}"') == 1

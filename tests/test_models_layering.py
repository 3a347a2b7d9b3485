"""The model layer's arrows point one way, read off the source with
``ast``: ``ops/`` <- ``models/common.py``, ``models/llama.py`` and the
shared modules (``moe``, ``blocks``, ``hybrid_cache``) <- the family
files <- ``models.family``. What a later cut of ``models/`` may lean on
(ROADMAP.md D13)."""

import ast
import os

import pytest

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "gofr_tpu")
MODELS = {f[:-3] for f in os.listdir(os.path.join(PKG, "models"))
          if f.endswith(".py") and f != "__init__.py"}
FAMILIES = {"deepseek_v3", "solar_open2", "laguna", "lfm2", "nemotron_h",
            "dots3_note", "ouro", "sdar"}
# what the engine asks of every family through ``models.family(cfg)``
# (docs/tpu/serving-engine.md, "What a new family touches")
ENTRY_POINTS = ("init", "init_cache", "get_rope_tables", "prefill_kv",
                "write_kv", "prefill_chunk", "decode_step",
                "decode_kv_block", "kv_layout", "kv_tables", "chunk_block",
                "unsupported_options", "serving_stats", "forward",
                "RECOMPUTABLE")


def _sources():
    """(path under gofr_tpu/ with dots, tree) of every module."""
    for root, _, files in os.walk(PKG):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as f:
                    tree = ast.parse(f.read(), path)
                rel = os.path.relpath(path, os.path.dirname(PKG))[:-3]
                yield rel.replace(os.sep, "."), tree


def _imports(module: str, tree):
    """(absolute module imported from, name imported or None, the name it
    is bound to, line) of every import in ``tree``."""
    package = module.rsplit(".", 1)[0]
    if module.endswith(".__init__"):
        package = module[:-len(".__init__")]
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, None, a.asname or a.name.split(".")[0], \
                    node.lineno
        elif isinstance(node, ast.ImportFrom):
            base = package.split(".")
            if node.level:
                base = base[:len(base) - (node.level - 1)]
                source = ".".join(base + ([node.module] if node.module
                                          else []))
            else:
                source = node.module
            for a in node.names:
                yield source, a.name, a.asname or a.name, node.lineno


def _reached(source: str, name: str | None):
    """(the module of ``gofr_tpu/models/`` an import reaches or None,
    whether ``name`` is a name taken out of it and not the module)."""
    head, _, last = source.rpartition(".")
    if head == "gofr_tpu.models" and last in MODELS:
        return last, name is not None
    if source == "gofr_tpu.models" and name in MODELS:
        return name, False
    return None, False


def _own(module: str) -> str | None:
    head, _, last = module.rpartition(".")
    return last if head == "gofr_tpu.models" else None


def _private_names_of_another_models_module():
    found = []
    for module, tree in _sources():
        bound = {}
        for source, name, alias, line in _imports(module, tree):
            target, inside = _reached(source, name)
            if target is None or target == _own(module):
                continue
            if not inside:
                bound[alias] = target       # the module itself, by a name
            elif name.startswith("_"):
                found.append(f"{module}:{line} imports {target}.{name}")
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) and node.attr.startswith("_") \
                    and not node.attr.startswith("__"):
                of = node.value
                target = bound.get(of.id) if isinstance(of, ast.Name) else (
                    of.attr if isinstance(of, ast.Attribute)
                    and of.attr in MODELS - {_own(module)} else None)
                if target:
                    found.append(f"{module}:{node.lineno} names "
                                 f"{target}.{node.attr}")
    return found


def _imports_against_the_arrows():
    """A family importing a sibling family; ``ops/`` importing ``models``
    or ``tpu``."""
    found = []
    for module, tree in _sources():
        for source, name, _, line in _imports(module, tree):
            target = _reached(source, name)[0]
            if _own(module) in FAMILIES and target in FAMILIES \
                    and target != _own(module):
                found.append(f"{module}:{line} imports {target}")
            if module.startswith("gofr_tpu.ops.") and any(
                    f"{source}.{name}.".startswith(f"gofr_tpu.{layer}.")
                    for layer in ("models", "tpu")):
                found.append(f"{module}:{line} imports {source}")
    return found


def _functions_that_walk_the_serving_options():
    """Every function under models/ but one (``common.refused_options``):
    the test of ``serving_role`` marks the walk over the engine's options."""
    def walks(fn):
        return any(isinstance(n, ast.Compare) and isinstance(n.left, ast.Name)
                   and n.left.id == "serving_role"
                   and isinstance(n.ops[0], ast.NotIn) for n in ast.walk(fn))

    found = [f"{module}.{node.name}" for module, tree in _sources()
             if module.startswith("gofr_tpu.models.")
             for node in ast.walk(tree)
             if isinstance(node, ast.FunctionDef) and walks(node)]
    assert found, "no function tests serving_role: the marker moved"
    return found[1:]


@pytest.mark.parametrize("breaches", [
    _private_names_of_another_models_module, _imports_against_the_arrows,
    _functions_that_walk_the_serving_options],
    ids=lambda f: f.__name__.strip("_"))
def test_the_model_layer_keeps_its_rule(breaches):
    assert breaches() == []


@pytest.mark.parametrize("name", sorted(FAMILIES | {"llama"}))
def test_a_family_gives_the_engine_every_entry_point(name):
    import importlib

    fam = importlib.import_module(f"gofr_tpu.models.{name}")
    assert [n for n in ENTRY_POINTS if not hasattr(fam, n)] == []


def test_only_the_block_family_says_a_step_is_a_pass():
    """``serving_stats()["diffusion"]`` is how a family tells the engine
    that its decode step is a pass over a block and that its prefill
    yields no token; the three names its decode program asks beside the
    entry points are its own."""
    from gofr_tpu.models import LLAMA_CONFIGS, family, sdar

    said = {name: family(cfg).serving_stats(cfg, 4).get("diffusion")
            for name, cfg in LLAMA_CONFIGS.items() if name.startswith("tiny")}
    assert [n for n, d in said.items() if d] == ["tiny-diffusion-moe"]
    assert said["tiny-diffusion-moe"]["block_length"] == 4
    for name in ("candidates", "commits", "logits"):
        assert callable(getattr(sdar, name))

"""exec API-parity tool tests (reference: api_validation/.../
ApiValidation.scala:27-60 signature diffing)."""

from spark_rapids_tpu.tools.api_validation import validate


def test_exec_api_parity_clean():
    errors, lines = validate()
    assert errors == [], errors
    assert any("HashAggregateExec" in l for l in lines)


def test_every_known_exec_covered():
    # the report must mention the headline operators so a future rename
    # can't silently drop them from validation
    _, lines = validate()
    text = "\n".join(lines)
    for op in ("FilterExec", "ProjectExec", "SortExec", "WindowExec",
               "ShuffleExchangeExec", "ExpandExec", "GenerateExec",
               "WriteExec"):
        assert op in text, op


def test_configs_doc_is_what_the_registry_generates():
    """docs/configs.md is ``conf.help_text()``: regenerate it with a conf."""
    import os
    from spark_rapids_tpu.config import conf
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "configs.md")
    with open(path) as f:
        assert f.read() == conf.help_text() + "\n"

"""One module per served architecture, named by the ``arch`` of a
backbone's entry under ``served.models``: its layer functions and
``forward(params, tokens, m, prompt_len, at, lower=None) -> (logits,
dropped routings)``."""

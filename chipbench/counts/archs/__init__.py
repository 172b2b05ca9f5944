"""One module per served architecture, named by the ``arch`` of a
backbone's entry under ``served.models``: ``layer_weights(m)``,
``prefill(m, s)``, ``decode(m, batch, pos)``, ``kernels_prefill(m, s)``
and ``kernels_decode(m, batch, pos)``, as ``chipbench/counts/models.py``
describes them."""

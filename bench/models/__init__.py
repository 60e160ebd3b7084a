"""The benchmark's reference architectures, one module each, found by the
name in a configuration file's ``"reference"`` key
(``bench/models/<name>.py``). None imports the program.

A module gives, for the configuration file's ``model`` block ``m``:

  layer_kinds(m)        the kind of every layer, in order
  param_shapes(m)       the weights' leaf shapes: head leaves beside
                        ``layers``, which holds the layers' leaves stacked
                        on a leading axis; where the layers are of more
                        than one kind, ``layers[kind]`` holds each kind's
                        stack, its rows in layer order
  fan_in(name, shape)   a weight's fan-in (init scale 1/sqrt(fan_in)), or
                        None for a norm scale or a bias
  DECAYED               the leaf names Adam's weight decay applies to
  embed(head, tokens)   the first layer's input
  layer(m, kind, quant, lp, x)
                        one layer: ``(x, aux)``, ``aux`` None or a scalar
                        added to the loss of the row
  head_loss(m, quant, head, x, labels)
                        mean next-token cross-entropy of the last layer's
                        output
  to_program(t, cfg), from_program(p, cfg)
                        the weights re-keyed to and from the program's
                        layout for ``cfg``; a module raises where it cannot
                        map ``cfg``
  model_flops_train(m, b, s)
                        model FLOPs of one training step over b rows of s
                        tokens, which ``mfu`` divides by the chip's peak

``bench/reference.py`` builds the weights, the whole-model loss and the
layer-by-layer trainer from these.
"""

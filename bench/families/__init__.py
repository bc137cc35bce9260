"""One module per model family: rows made from the seed, model built
through the program's normal path (``repro.models.bayes_glm``)."""
